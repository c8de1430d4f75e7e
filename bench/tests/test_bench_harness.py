"""The benchmark's yardstick on the CPU: cell resolution, peaks, required
work, trace reduction and the entry's refusals."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.drivers import train_classifier as tc  # noqa: E402
from bench.harness import cell as cell_lib  # noqa: E402
from bench.harness import flops, peaks, trace  # noqa: E402


def _config(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


# ----------------------------------------------------------------------
# required work
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "name, paper_b",
    [("resnet18-cifar10", 285.32), ("resnet18-imagenet1k", 3495.14)],
)
def test_walk_matches_paper_table4(name, paper_b):
    cfg = _config(name)
    got = flops.paper_backward_flops(cfg, cfg["batch"]) / 1e9
    # Eq. 6/7 over the executed geometry lands within 0.03% of the paper's
    # printed per-iteration count (the paper's own rounding of small terms)
    assert got == pytest.approx(paper_b, rel=5e-4)
    assert cfg["paper_backward_flops_per_iter"] == pytest.approx(paper_b * 1e9)


def test_sparse_walk_counts_kept_blocks():
    cfg = _config("resnet18-cifar10")
    dense = dict(drop_rate=0.0, granularity="block", block_size=128)
    sparse = dict(dense, drop_rate=0.8)
    # 64/128 channels: one block, all kept; 256: 1 of 2; 512: 1 of 4
    assert [flops.kept_channels(c, sparse) for c in (64, 128, 256, 512)] == [64, 128, 128, 128]
    d, s = flops.step_flops(cfg, 128, dense), flops.step_flops(cfg, 128, sparse)
    assert 0.42e12 < d < 0.43e12 and 0.33e12 < s < 0.35e12
    calls = flops.sparse_kernel_calls(cfg, 128, sparse)
    assert len(calls) == 39  # 20 conv sites x (dX, dW), no dX at the stem
    assert {c.bound(197e12, 819e9) for c in calls} <= {"compute", "memory"}


def test_peaks_refuse_unknown_device():
    assert peaks.for_kind("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.for_kind("TPU v9 imaginary")


# ----------------------------------------------------------------------
# trace reduction on a hand-built trace
# ----------------------------------------------------------------------
def _ev(name, start, end, op="fusion"):
    return trace.Event(name, start, end, op)


def _hand_trace():
    spans = [
        _ev(trace.STEP_SPAN, 0.0, 1.0),
        _ev("bench/batch_transfer", 0.0, 0.2),
        _ev("bench/dispatch", 0.2, 0.3),
        _ev("bench/loss_read", 0.3, 1.0),
        _ev(trace.STEP_SPAN, 1.0, 2.0),
        _ev("bench/batch_transfer", 1.0, 1.25),
        _ev("bench/dispatch", 1.25, 1.3),
        _ev("bench/loss_read", 1.3, 2.0),
    ]
    ops = [
        _ev("fusion.1", 0.3, 0.6),
        _ev("transpose_jvp_jit_conv_dw_fused_scatter___.4", 0.5, 0.8, "custom-call"),
        _ev("fusion.1", 1.3, 1.6),
        _ev("transpose_jvp_jit_conv_dw_fused_scatter___.4", 1.6, 1.9, "custom-call"),
        _ev("fusion.2_conv_dw_fused", 1.9, 1.95),  # not a custom call: no kernel
        _ev("fusion.9", 2.5, 3.0),  # after the window: clipped away
    ]
    return trace.Trace({"/device:TPU:0": ops}, spans)


def test_trace_busy_union_and_kernel_sum():
    red = trace.reduce(_hand_trace(), steps=2, kernels=("conv_dw_fused",))
    assert red.window_s == pytest.approx(2.0)
    # union [0.3, 0.8] and [1.3, 1.95]: overlapping ops count once
    assert red.busy_s == pytest.approx(1.15)
    assert red.kernel_s == pytest.approx(0.6)
    names = dict(red.device_ops)
    assert names["fusion.1"] == pytest.approx(0.6)
    assert names["transpose_jvp_jit_conv_dw_fused_scatter___.4 [conv_dw_fused]"] == pytest.approx(0.6)


def test_trace_parses_hlo_event_names():
    assert trace.parse_op(
        "%transpose_jvp_jit_conv_dx_fused___.32 = f32[4352,1,34,64]{3,2,1,0:T(8,128)} "
        "custom-call(s32[1]{0:T(128)} %constant.64, f32[1]{0} %p)"
    ) == ("transpose_jvp_jit_conv_dx_fused___.32", "custom-call")
    assert trace.parse_op(
        "%multiply_reduce_fusion.10 = (f32[64]{0:T(128)S(1)}, f32[128,64,32,32]{0,1,3,2:T(8,128)}) "
        "fusion(f32[128,64,32,32]{0,1,3,2:T(8,128)S(1)} %custom-call.7), kind=kOutput"
    ) == ("multiply_reduce_fusion.10", "fusion")


def test_trace_idle_gaps_named_by_host_span():
    red = trace.reduce(_hand_trace(), steps=2)
    gaps = red.idle_gaps
    # gaps: [0, 0.3] transfer then dispatch, [0.8, 1.3] loss read then
    # transfer, [1.95, 2.0] loss read; longest first, each named by the
    # host span that overlaps it most
    assert [g[0] for g in gaps] == ["batch_transfer", "batch_transfer", "loss_read"]
    assert [g[1] for g in gaps] == pytest.approx([0.5, 0.3, 0.05])


def test_trace_window_needs_its_steps():
    with pytest.raises(ValueError, match="step spans"):
        trace.window(_hand_trace().spans, 3)


# ----------------------------------------------------------------------
# cells are data: a new mix plus an entry resolves with no other edit
# ----------------------------------------------------------------------
def _copy_tree(dst: pathlib.Path):
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))


def test_new_mix_and_entry_resolve_to_a_cell(tmp_path):
    _copy_tree(tmp_path)
    mix = json.loads((tmp_path / "bench/mixes/ssprop80.json").read_text())
    mix["drop_rate"] = 0.5
    (tmp_path / "bench/mixes/ssprop50.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/resnet18-cifar10.ssprop50.json").write_text(
        (tmp_path / "bench/limits/resnet18-cifar10.ssprop80.json").read_text()
    )
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "resnet18-cifar10.ssprop50", "config": "resnet18-cifar10",
        "traffic": "ssprop50", "chips": 1, "why": "test",
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cell_lib.resolve(tmp_path, "resnet18-cifar10.ssprop50")
    assert cell.mix["drop_rate"] == 0.5
    assert cell.config["batch"] == 128
    assert cell.driver is tc
    assert cell.limits
    assert {m.name for m in cell.end_to_end} == {"train_images_per_s", "setup_s"}
    # per-layer metrics that list their cells do not extend to a new one
    assert cell.per_layer == ()
    assert cell_lib.reference_module(cell).__name__ == "bench.configs.resnet_reference"


@pytest.mark.parametrize(
    "workload, layers",
    [
        ("resnet18-cifar10.ssprop80", {"train_mfu", "sparse_bwd_kernel_roofline", "device_idle_share"}),
        ("resnet18-imagenet1k.dense", {"train_mfu", "device_idle_share"}),
    ],
)
def test_committed_cells_resolve(workload, layers):
    cell = cell_lib.resolve(ROOT, workload)
    assert set(cell.readers) == layers
    assert all(hasattr(r, "read") for r in cell.readers.values())


# ----------------------------------------------------------------------
# the entry refuses to measure without a chip
# ----------------------------------------------------------------------
def _run_entry(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet18-cifar10.dense",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_entry(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    _copy_tree(tmp_path)
    proc = _run_entry(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
