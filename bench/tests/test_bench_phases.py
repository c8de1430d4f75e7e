"""Attribution of device time to the step's phases, conv sites and ssProp
stages (``harness.phases``, ``bench/attribute.py``), on the CPU: op names
by hand, a hand-built trace, and the tiny step's compiled HLO."""
from __future__ import annotations

import dataclasses
import types

import pytest

from bench import attribute
from bench.drivers import train_classifier as tc
from bench.harness import cell as cell_lib
from bench.harness import phases, trace

SITES = ("stem", "block_0/conv1", "block_1/conv1", "block_11/conv1", "block_4/down")


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(classifier_step)/jvp(block_0/conv1)/conv_general_dilated",
         ("forward", "block_0/conv1", "")),
        ("jit(classifier_step)/transpose(jvp(block_0/conv1))/ssprop_select/reduce_sum",
         ("backward", "block_0/conv1", "select")),
        ("jit(classifier_step)/transpose(jvp(block_1/conv1))/ssprop_contract/"
         "jit(conv_dx_fused)/while/body/and", ("backward", "block_1/conv1", "contract")),
        ("jit(classifier_step)/transpose(jvp(block_11/conv1))/convert_element_type",
         ("backward", "block_11/conv1", "")),
        ("jit(classifier_step)/transpose(jvp(block_4/down))/ssprop_contract/"
         "jit(dw_gathered_scatter)/scatter", ("backward", "block_4/down", "contract")),
        ("jit(classifier_step)/adam/sub", ("optimizer", "", "")),
        ("jit(classifier_step)/jvp()/reduce_sum", ("forward", "", "")),
        ("jit(classifier_step)/transpose(jvp())/mul", ("backward", "", "")),
        ("", ("unattributed", "", "")),
    ],
    ids=["forward", "select", "contract", "backward_other", "down", "adam", "unscoped",
         "unscoped_backward", "no_name"],
)
def test_phase_of_op_name(op_name, want):
    assert phases.phase_of(op_name, SITES) == want


def test_op_names_reads_instruction_metadata():
    hlo = "\n".join([
        "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0), metadata={op_name="params[\\\'stem\\\'][\\\'w\\\']"}',
        '  %conv_dx_fused.6 = f32[4]{0} custom-call(f32[4]{0} %p), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/transpose(jvp(stem))/ssprop_contract/jit(conv_dx_fused)" '
        'source_file="x.py" source_line=3}',
        "  %copy.2 = f32[4]{0} copy(f32[4]{0} %conv_dx_fused.6)",
        '  ROOT %fusion.3 = (f32[4]{0}, f32[]) fusion(f32[4]{0} %copy.2), kind=kLoop, '
        'metadata={op_type="sub" op_name="jit(step)/adam/sub"}',
        "}",
    ])
    assert phases.op_names(hlo) == {
        "p": "params['stem']['w']",
        "conv_dx_fused.6": "jit(step)/transpose(jvp(stem))/ssprop_contract/jit(conv_dx_fused)",
        "fusion.3": "jit(step)/adam/sub",
    }


# A fusion as XLA builds it on the TPU: the batch norm's backward, a
# site's importance and the contraction's layout change fused into one
# operation whose own op_name names only the first; inside it a nested
# fusion with Adam's update and an instruction of two merged names.
FUSED_HLO = "\n".join([
    "%nested.2 (param_0.9: f32[64]) -> f32[64] {",
    "  %param_0.9 = f32[64]{0} parameter(0)",
    '  %sub.3 = f32[64]{0} subtract(%param_0.9, %param_0.9), metadata={op_name="jit(step)/adam/sub"}',
    "}",
    "",
    "%fused_computation.7 (param_0.1: f32[8,16], param_1.2: f32[16], param_2.3: f32[64]) -> (f32[16], f32[8,16]) {",
    "  %param_0.1 = f32[8,16]{1,0:T(8,128)} parameter(0)",
    "  %param_1.2 = f32[16]{0} parameter(1)",
    '  %b.1 = f32[8,16]{1,0} broadcast(%param_1.2), dimensions={1}, metadata={op_name="jit(step)/transpose(jvp())/mul"}',
    '  %mul.1 = f32[8,16]{1,0} multiply(%param_0.1, %b.1), metadata={op_name="jit(step)/transpose(jvp())/mul"}',
    '  %abs.1 = f32[8,16]{1,0} abs(%mul.1), metadata={op_name="jit(step)/transpose(jvp(block_0/conv1))/ssprop_select/abs"}',
    "  %c.1 = f32[] constant(0)",
    '  %reduce.1 = f32[16]{0} reduce(%abs.1, %c.1), dimensions={0}, to_apply=%region_1.1, '
    'metadata={op_name="jit(step)/transpose(jvp(block_0/conv1))/ssprop_select/reduce_sum"}',
    '  %t.1 = f32[8,16]{1,0} transpose(%mul.1), dimensions={0,1}, metadata={op_name='
    '"jit(step)/transpose(jvp(block_0/conv1))/ssprop_contract/transpose;jit(step)/jvp()/mul"}',
    "  %param_2.3 = f32[64]{0} parameter(2)",
    "  %fusion.5 = f32[64]{0} fusion(%param_2.3), kind=kLoop, calls=%nested.2",
    "  ROOT %tuple.1 = (f32[16]{0}, f32[8,16]{1,0}) tuple(%reduce.1, %t.1)",
    "}",
    "",
    "ENTRY %main.1 (p0: f32[8,16], p1: f32[16], p2: f32[64]) -> (f32[16], f32[8,16]) {",
    "  %p0 = f32[8,16]{1,0} parameter(0)",
    "  %p1 = f32[16]{0} parameter(1)",
    "  %p2 = f32[64]{0} parameter(2)",
    "  ROOT %fusion.7 = (f32[16]{0}, f32[8,16]{1,0}) fusion(%p0, %p1, /*index=2*/%p2), kind=kLoop, "
    'calls=%fused_computation.7, metadata={op_name="jit(step)/transpose(jvp())/mul" stack_frame_id=4}',
    '  %copy.9 = f32[16]{0} copy(%p1), metadata={op_name="jit(step)/adam/copy;jit(step)/jvp()/copy"}',
    "}",
])


def test_work_opens_fusions_and_weighs_their_instructions():
    runs = phases.work(FUSED_HLO)
    # every array of the fused computation holds 128 elements but Adam's
    # 64; broadcasts, constants, parameters and tuples weigh nothing
    assert sorted(runs["fusion.7"]) == sorted([
        ("jit(step)/transpose(jvp())/mul", 128),
        ("jit(step)/transpose(jvp(block_0/conv1))/ssprop_select/abs", 128),
        ("jit(step)/transpose(jvp(block_0/conv1))/ssprop_select/reduce_sum", 128),
        ("jit(step)/transpose(jvp(block_0/conv1))/ssprop_contract/transpose", 64),
        ("jit(step)/jvp()/mul", 64),
        ("jit(step)/adam/sub", 64),
    ])
    assert runs["copy.9"] == (("jit(step)/adam/copy", 0.5), ("jit(step)/jvp()/copy", 0.5))
    assert runs["p0"] == ()
    shares = phases.split(runs["fusion.7"], ("block_0/conv1",))
    assert shares == pytest.approx({
        ("backward", "", ""): 128 / 576,
        ("backward", "block_0/conv1", "select"): 256 / 576,
        ("backward", "block_0/conv1", "contract"): 64 / 576,
        ("forward", "", ""): 64 / 576,
        ("optimizer", "", ""): 64 / 576,
    })
    assert phases.split((), ()) == {("unattributed", "", ""): 1.0}


def test_work_weighs_contractions_by_multiply_adds():
    hlo = "\n".join([
        "%fused_computation.3 (param_0.1: f32[8,6,6,16], param_1.2: f32[3,3,16,32]) -> f32[8,4,4,32] {",
        "  %param_0.1 = f32[8,6,6,16]{3,2,1,0} parameter(0)",
        "  %param_1.2 = f32[3,3,16,32]{3,2,1,0} parameter(1)",
        "  %convolution.1 = f32[8,4,4,32]{3,2,1,0} convolution(%param_0.1, %param_1.2), "
        'window={size=3x3}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/transpose(jvp(stem))/conv"}',
        '  ROOT %sub.1 = f32[8,4,4,32]{3,2,1,0} subtract(%convolution.1, %convolution.1), metadata={op_name="jit(step)/adam/sub"}',
        "}",
        "",
        "ENTRY %main.2 (p0: f32[8,6,6,16], p1: f32[3,3,16,32], p2: f32[8,16], p3: f32[16,4]) -> f32[8,4,4,32] {",
        "  %p0 = f32[8,6,6,16]{3,2,1,0} parameter(0)",
        "  %p1 = f32[3,3,16,32]{3,2,1,0} parameter(1)",
        "  %p2 = f32[8,16]{1,0} parameter(2)",
        "  %p3 = f32[16,4]{1,0} parameter(3)",
        "  %dot.4 = f32[8,4]{1,0} dot(f32[8,16]{1,0} %p2, f32[16,4]{1,0} %p3), lhs_contracting_dims={1}, "
        'rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(head)/dot_general"}',
        "  ROOT %fusion.3 = f32[8,4,4,32]{3,2,1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.3",
        "}",
    ])
    runs = phases.work(hlo)
    # 4096 outputs of 3 x 3 x 16 multiply-adds each, over 64; the
    # subtract reads and writes 4096 elements
    assert runs["fusion.3"] == (
        ("jit(step)/transpose(jvp(stem))/conv", 4096 * 144 / 64),
        ("jit(step)/adam/sub", 4096),
    )
    # alone in its instruction, a dot's weight is its own
    assert runs["dot.4"] == (("jit(step)/jvp(head)/dot_general", 1.0),)
    assert phases._weight(phases._parse(hlo)["main.2"][4], {"p2": (8, 16)}) == 8 * 4 * 16 / 64


def test_reduce_splits_mixed_operations_and_bounds_them():
    runs = phases.work(FUSED_HLO) | RUNS
    spans = [_ev(trace.STEP_SPAN, 0.0, 1.0)]
    ops = [_ev("fusion.7", 0.0, 0.576), _ev("sel.1", 0.6, 0.7), _ev("adam.1", 0.7, 0.8)]
    ph = phases.reduce(trace.Trace({"/device:TPU:0": ops}, spans), 1, runs, ("block_0/conv1",))
    assert ph.op_s == pytest.approx(0.776)
    assert ph.mixed_s == pytest.approx(0.576)
    assert ph.phase_s == pytest.approx(
        {"forward": 0.064, "backward": 0.448 + 0.1, "optimizer": 0.164, "unattributed": 0.0}
    )
    assert ph.select_s == pytest.approx(0.256 + 0.1)
    assert ph.site_s["block_0/conv1"] == pytest.approx(
        {"forward": 0.0, "select": 0.356, "contract": 0.064, "backward_other": 0.0}
    )
    # what ran a phase (or the selection) alone, and what ran any of it
    want = {
        "forward": [0.0, 0.576],
        "backward": [0.1, 0.676],
        "optimizer": [0.1, 0.676],
        "select": [0.1, 0.676],
    }
    assert ph.bounds_s.keys() == want.keys()
    for kind, bounds in want.items():
        assert ph.bounds_s[kind] == pytest.approx(bounds), kind
    assert ph.per_step_ms()["bounds_ms"]["select"] == pytest.approx([100.0, 676.0])


def _ev(name, start, end, op="fusion"):
    return trace.Event(name, start, end, op)


def _hand_trace():
    spans = [_ev(trace.STEP_SPAN, 0.0, 1.0), _ev(trace.STEP_SPAN, 1.0, 2.0)]
    ops = [
        _ev("fwd.1", 0.0, 0.2),
        _ev("sel.1", 0.2, 0.25),
        _ev("dx.1", 0.25, 0.55, "custom-call"),
        _ev("cast.1", 0.55, 0.6),
        _ev("bn_bwd.1", 0.6, 0.7),
        _ev("adam.1", 0.7, 0.8),
        _ev("copy.1", 0.8, 0.85),
        _ev("fwd.1", 1.0, 1.2),
        _ev("sel.1", 1.2, 1.25),
        _ev("dx.1", 1.25, 1.55, "custom-call"),
        _ev("adam.1", 1.9, 2.1),  # half past the window: clipped
        _ev("fwd.1", 2.5, 2.7),  # after the window
    ]
    return trace.Trace({"/device:TPU:0": ops}, spans)


NAMES = {
    "fwd.1": "jit(step)/jvp(block_0/conv1)/conv_general_dilated",
    "sel.1": "jit(step)/transpose(jvp(block_0/conv1))/ssprop_select/reduce_max",
    "dx.1": "jit(step)/transpose(jvp(block_0/conv1))/ssprop_contract/jit(conv_dx_fused)",
    "cast.1": "jit(step)/transpose(jvp(block_0/conv1))/convert_element_type",
    "bn_bwd.1": "jit(step)/transpose(jvp())/mul",
    "adam.1": "jit(step)/adam/mul",
}


def _runs(names):
    return {k: ((v, 1.0),) for k, v in names.items()}


RUNS = _runs(NAMES)


def test_reduce_splits_the_window_by_phase_site_and_stage():
    ph = phases.reduce(_hand_trace(), 2, RUNS, ("stem", "block_0/conv1"))
    assert ph.steps == 2
    assert ph.op_s == pytest.approx(1.5)
    assert ph.phase_s == pytest.approx(
        {"forward": 0.4, "backward": 0.85, "optimizer": 0.2, "unattributed": 0.05}
    )
    assert sum(ph.phase_s.values()) == pytest.approx(ph.op_s)
    assert ph.select_s == pytest.approx(0.1)
    assert ph.site_s["block_0/conv1"] == pytest.approx(
        {"forward": 0.4, "select": 0.1, "contract": 0.6, "backward_other": 0.05}
    )
    assert ph.site_s["stem"] == dict.fromkeys(phases.SITE_KEYS, 0.0)
    assert ph.attributed_share == pytest.approx(1 - 0.05 / 1.5)
    assert ph.unattributed == [["copy.1", pytest.approx(0.05)]]
    table = ph.per_step_ms()
    assert table["phase_ms"]["backward"] == pytest.approx(425.0)
    assert table["site_ms"]["block_0/conv1"]["contract"] == pytest.approx(300.0)


def test_readers_read_the_split():
    ph = phases.reduce(_hand_trace(), 2, RUNS, ("block_0/conv1",))
    got = attribute.read_metrics(ph)
    assert got["step_fwd_ms"] == pytest.approx(200.0)
    assert got["step_bwd_ms"] == pytest.approx(425.0)
    assert got["step_opt_ms"] == pytest.approx(100.0)
    assert got["sparse_select_share"] == pytest.approx(100 * 0.1 / 0.85)


@pytest.mark.parametrize("metric", attribute.METRICS)
def test_readers_return_nothing_without_their_data(metric):
    from importlib import import_module

    reader = import_module(f"bench.metrics.{metric}")
    # train_classifier's observation carries no attribution
    obs = tc.Observation(None, None, 2, None, 0.0, 0.0)
    assert reader.read(obs) is None
    assert reader.read(types.SimpleNamespace(phases=None)) is None
    # a program without the scopes: no optimizer operation, no selection
    ph = phases.reduce(_hand_trace(), 2, _runs({k: v for k, v in NAMES.items() if "adam" not in k
                                                and "sel" not in k}), ())
    assert ph.phase_s["optimizer"] == 0 and ph.select_s == 0
    unscoped = {"step_opt_ms": None, "sparse_select_share": None}
    if metric in unscoped:
        assert reader.read(types.SimpleNamespace(phases=ph)) is None
    else:
        assert reader.read(types.SimpleNamespace(phases=ph)) > 0
    empty = dataclasses.replace(ph, phase_s=dict.fromkeys(phases.PHASES, 0.0))
    assert reader.read(types.SimpleNamespace(phases=empty)) is None


# ----------------------------------------------------------------------
# the tiny step's compiled HLO names every site, stage and the optimizer
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def compiled(tiny_root):
    def names(workload):
        cell = cell_lib.resolve(tiny_root, workload)
        return cell, phases.op_names(tc.build(cell, 0)[0].step.as_text())

    return names


def test_tiny_sparse_step_names_every_site_stage_and_adam(compiled):
    from repro.core.policy import policy_for
    from repro.models import resnet

    cell, names = compiled("resnet18-cifar10.ssprop80")
    sites = attribute.conv_sites(cell)
    assert len(sites) == 20
    seen = {"forward": set(), "backward": set(), "select": set(), "optimizer": 0}
    for op_name in names.values():
        phase, site, stage = phases.phase_of(op_name, sites)
        if phase == "optimizer":
            seen["optimizer"] += 1
        elif site:
            seen[phase].add(site)
            if stage == "select":
                seen["select"].add(site)
    assert seen["forward"] == set(sites)
    assert seen["backward"] == set(sites)
    # selection runs where blocks are dropped: a site of one 128-channel
    # block keeps it whatever its importance, and XLA folds the choice away
    policy = tc._policy(cell.mix)
    dropping = {
        site for site, _, c_out, *_ in resnet.iter_conv_shapes("resnet18", (3, 8, 8))
        if policy_for(policy, site).keep_count(c_out) < -(-c_out // policy.block_size)
    }
    assert seen["select"] == dropping
    assert len(dropping) == 10  # the 256- and 512-channel convs
    assert seen["optimizer"] > 0


def test_tiny_dense_step_selects_nothing(compiled):
    cell, names = compiled("resnet18-cifar10.dense")
    assert not any("ssprop_select" in n for n in names.values())
    assert any("ssprop_contract" in n for n in names.values())
    assert any(phases.phase_of(n, ())[0] == "optimizer" for n in names.values())


def test_attribute_reads_a_run_directory(tiny_root, tmp_path, monkeypatch):
    import json

    cell = cell_lib.resolve(tiny_root, "resnet18-cifar10.ssprop80")
    hlo = "\n".join(
        f'  %{name} = f32[4]{{0}} fusion(f32[4]{{0}} %p), metadata={{op_name="{op}"}}'
        for name, op in NAMES.items()
    ) + "\n  %copy.1 = f32[4]{0} copy(f32[4]{0} %p)"
    monkeypatch.setattr(trace, "load", lambda d: _hand_trace())
    # two traced steps, the step in which the profiler stopped, two more
    (tmp_path / "steps.json").write_text(json.dumps({"t0": 0.0, "ends": [1.0, 2.0, 3.5, 4.0, 4.5]}))
    split = attribute.attribute(cell, tmp_path / "trace", hlo)
    table = split["table"]
    assert table["steps"] == 2
    assert table["phase_ms"]["optimizer"] == pytest.approx(100.0)
    assert sum(table["phase_ms"].values()) == pytest.approx(table["op_ms"])
    assert set(table["site_ms"]) == set(attribute.conv_sites(cell))
    assert table["unknown_share"] == 0.0  # every event names an instruction
    assert table["kernel_ms"] == pytest.approx(0.0)  # "dx.1" is no kernel's name
    assert table["images_per_s"] == pytest.approx({"traced": 8.0, "untraced": 16.0})
    assert split["top_sites_bwd_ms"][0] == ["block_0/conv1", pytest.approx(375.0)]
    assert split["metrics"]["step_opt_ms"] == pytest.approx(100.0)
