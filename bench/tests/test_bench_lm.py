"""The LM training cell on the CPU, at a size a test run holds.

``qwen2.5-3b-train.1x4`` cut to 2 layers of width 128 (the training
CLI's ``--reduced`` qwen2.5), 2 sequences of 32 tokens, on one device: a
sound run reads ``correct``; so do not the float8 control and the planted
faults of the reference put in the program's place, nor a program step
that leaves its state unchanged. Beside them, the LM step's FLOPs against
a hand count and the three per-layer readers on a hand-built trace.
"""
from __future__ import annotations

import dataclasses
import json
import time
import types

import pytest

from bench import run as bench_run
from bench.drivers import train_lm
from bench.harness import cell as cell_lib
from bench.harness import compare, lm, trace
from bench.tests.conftest import ROOT

CELL = "qwen2.5-3b-train.1x4"
SEED = 2**33 + 12345  # wider than 32 bits, as a benchmark seed may be
TINY = {
    "program_args": ["--arch", "qwen2.5-3b", "--reduced"], "num_hidden_layers": 2,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 256, "vocab_size": 512, "param_dtype": "float32", "remat": False,
}


@pytest.fixture(scope="module")
def lm_root(tiny_root):
    """The committed benchmark with the LM cell cut to the CLI's reduced
    qwen2.5 on one device."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 1
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    f = tiny_root / "bench/configs/lm/qwen2.5-3b-train.json"
    f.write_text(json.dumps({**json.loads(f.read_text()), **TINY}))
    m = tiny_root / "bench/mixes/1x4.json"
    m.write_text(json.dumps({**json.loads(m.read_text()), "mesh": [1, 1], "global_batch": 2, "seq_len": 32}))
    return tiny_root


def _run(root, seconds="0.5"):
    args = bench_run.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", seconds, "--trace", "0"])
    return bench_run.execute(args, root=root, require_accelerator=False, t_start=time.perf_counter())


def test_sound_run_is_correct(lm_root):
    res = _run(lm_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}
    assert set(res["checks"]) == set(json.loads((ROOT / f"bench/limits/{CELL}.json").read_text()))


def test_unchanged_state_is_not_correct(lm_root, monkeypatch):
    from repro.launch import steps

    make = steps.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics

        return unchanged

    monkeypatch.setattr(steps, "make_train_step", broken)
    res = _run(lm_root)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > res["checks"]["change_gap"]["limit"]


@pytest.mark.parametrize(
    "kind", [{"precision": "fp8"}, {"fault": "half_batch"}, {"fault": "wrong_block"}, {"fault": "dw_x2"}],
    ids=["fp8", "half_batch", "wrong_block", "dw_x2"],
)
def test_control_and_faults_are_not_correct(lm_root, kind):
    # the MLP at 1024 channels, 8 blocks of which 2 are kept, so that the
    # least important blocks fall well below the kept ones (no program
    # runs here, so the width need not be the CLI's reduced one)
    cell = cell_lib.resolve(lm_root, CELL)
    cell = dataclasses.replace(cell, config={**cell.config, "intermediate_size": 1024})
    run = train_lm.reference_readings(cell, SEED, **kind)
    ref = train_lm.reference_readings(cell, SEED, follow=compare.picks(run["kept"]))
    checks = compare.checks(compare.gaps(run, ref, cell.limits), cell.limits)
    assert not compare.passed(checks), checks


def test_committed_cell_resolves_with_its_readers():
    cell = cell_lib.resolve(ROOT, CELL)
    assert cell.chips == 4 and cell.driver is train_lm
    assert set(cell.readers) == {"train_mfu", "tp_collective_share", "attn_core_share", "device_idle_share"}
    assert cell_lib.reference_module(cell).__name__ == "bench.configs.qwen2_reference"


def test_step_flops_hand_count():
    """One layer of width 8, 2 heads of 4 (1 kv head), MLP 16, vocab 10,
    1 sequence of 3 tokens; every projection keeps every channel but
    gate/up, which keep 8 of 16."""
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
           "num_key_value_heads": 1, "num_hidden_layers": 1, "vocab_size": 10}
    whole = {n: (1, 4, 2) for n in ("q", "o", "down")} | {n: (1, 4, 1) for n in ("k", "v")}
    sel = whole | {"gate": (2, 4, 1), "up": (2, 4, 1)}
    t = 3
    fwd = 2 * t * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 8 * 16 + 8 * 16 + 16 * 8)
    bwd = 4 * t * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 8 * 8 + 8 * 8 + 16 * 8)
    attn = 2 * 2 * 2 * 4 * (1 + 2 + 3)  # QK^T and PV over the causal pairs
    head = 2 * t * 8 * 10
    assert lm.step_flops(cfg, 1, 3, sel) == fwd + bwd + 3 * attn + 3 * head


def _ev(name, start, end, op=""):
    return trace.Event(name, start, end, op)


def test_collective_and_scope_shares_on_a_hand_built_trace():
    spans = [_ev(trace.STEP_SPAN, 0.0, 10.0)]
    chip = [
        _ev("while.9", 0.0, 8.0, "while"),  # a scan's loop, spanning its body's ops
        _ev("fusion.1", 0.0, 4.0, "fusion"),
        _ev("all-reduce.2", 4.0, 5.0, "all-reduce"),
        _ev("all-gather-start.3", 5.0, 5.5, "all-gather-start"),
        _ev("fusion.4", 6.0, 8.0, "fusion"),
    ]
    other = [_ev("fusion.1", 0.0, 4.0, "fusion"), _ev("all-reduce.2", 4.0, 8.0, "all-reduce")]
    tr = lm.leaf_ops(trace.Trace({"/device:TPU:0": chip, "/device:TPU:1": other}, spans))
    assert [e.name for e in tr.devices["/device:TPU:0"]] == [e.name for e in chip[1:]]
    # chip 0: 1.5 s of 7.5 busy; chip 1: 4 of 8
    assert lm.collective_share(tr, 1) == pytest.approx((1.5 / 7.5 + 4 / 8) / 2)
    runs = {
        "fusion.1": (("jit(f)/jvp(attn_core)/dot_general", 3.0), ("jit(f)/attn/q/dot_general", 1.0)),
        "fusion.4": (("jit(f)/transpose(jvp(attn_core))/exp", 1.0),),
    }
    secs = lm.scope_seconds(tr, 1, runs, ("attn_core", "lm_loss"))
    assert secs == {"attn_core": pytest.approx(2 * 4.0 * 0.75 + 2.0), "lm_loss": 0.0}


def test_readers_read_the_observation():
    from bench.metrics import attn_core_share, tp_collective_share, train_mfu

    obs = types.SimpleNamespace(
        steps=2, step_flops=2.5e11, peaks=types.SimpleNamespace(bf16_flops=1e12),
        reduction=types.SimpleNamespace(window_s=4.0), collective_share=0.25,
        scope_s={"attn_core": 1.0}, phases=types.SimpleNamespace(op_s=8.0),
    )
    assert train_mfu.read(obs) == pytest.approx(12.5)  # per chip
    assert tp_collective_share.read(obs) == pytest.approx(25.0)
    assert attn_core_share.read(obs) == pytest.approx(12.5)
    # a program without the scope or the share reads nothing
    empty = types.SimpleNamespace(scope_s={}, phases=types.SimpleNamespace(op_s=8.0))
    assert attn_core_share.read(empty) is None
    assert tp_collective_share.read(empty) is None


def test_config_states_what_the_program_runs():
    """The configuration file is the program's qwen2.5-3b at published
    widths, and states the per-shard selection its reference derives at
    the mix's drop rate on four shards."""
    from repro.configs.registry import get_config

    cell = cell_lib.resolve(ROOT, CELL)
    cfg, mix = cell.config, cell.mix
    full = get_config("qwen2.5-3b")
    assert {k: cfg[k] for k in train_lm.PUBLISHED} == {
        k: getattr(full, f) for k, f in train_lm.PUBLISHED.items()
    }
    assert full.norm_eps == 1e-6 and cfg["reduced"] == []
    stated = cfg["selection_at_drop_0.8"]
    ref = cell_lib.reference_module(cell)
    for name, (n, bs, keep) in ref.selection(cfg, mix, 4).items():
        s = stated[name]
        assert (s["shards"], s["block"]) == (n, bs), name
        assert s.get("kept_blocks_per_shard", s.get("kept_blocks")) == keep, name
        assert s["kept_channels"] == min(s["channels"], n * keep * bs), name
