"""The control and the planted faults, on the CPU at a size a test run
holds: the reference, run in float8 or with a fault planted, put in the
program's place and held to each cell's limits against the reference
that follows its blocks, must come out not correct."""
from __future__ import annotations

import pytest

from bench.drivers import train_classifier as tc
from bench.harness import cell as cell_lib
from bench.harness import compare

SEED = 2**33 + 12345  # wider than 32 bits, as the driver's seeds are
CELLS = [
    "resnet18-cifar10.ssprop80", "resnet18-imagenet1k.ssprop80",
    "resnet18-imagenet1k.dense", "resnet18-cifar10.dense",
]


def _stand_in(cell, **kw):
    """The reference, run as ``kw`` says, put in the program's place and
    held to the cell's limits against the reference that follows it."""
    run = tc.reference_readings(cell, SEED, **kw)
    ref = tc.reference_readings(cell, SEED, follow=compare.picks(run["kept"]))
    return compare.checks(compare.gaps(run, ref, cell.limits), cell.limits)


@pytest.mark.parametrize("workload", CELLS)
def test_fp8_control_is_not_correct(tiny_root, workload):
    checks = _stand_in(cell_lib.resolve(tiny_root, workload), precision="fp8")
    assert not compare.passed(checks), checks


@pytest.mark.parametrize(
    "workload, fault",
    [(w, f) for w in CELLS[:2] for f in ("wrong_block", "dw_x2", "half_batch")]
    + [(w, f) for w in CELLS[2:] for f in ("dw_x2", "half_batch")],
)
def test_planted_fault_is_not_correct(tiny_root, workload, fault):
    checks = _stand_in(cell_lib.resolve(tiny_root, workload), fault=fault)
    assert not compare.passed(checks), checks


def test_selection_gap_reads_the_kept_blocks():
    ref = {
        "importance": [{"s": [1.0, 0.99, 0.5, 0.2]}],
        "keep_blocks": {"s": 1, "dense": 1},
    }
    ref["importance"][0]["dense"] = [1.0]

    def run(*rows):
        return {"kept": [{"s": list(rows), "dense": [1.0]}]}

    assert compare.selection_gap(run(3.0, 0, 0, 0), ref) == 0.0
    assert compare.selection_gap(run(0, 2.0, 0, 0), ref) == pytest.approx(0.01)
    assert compare.selection_gap(run(0, 0, 0, 1.0), ref) == pytest.approx(0.8)
    assert compare.selection_gap(run(1.0, 1.0, 0, 0), ref) == 1.0
    picks = compare.picks(run(0, 2.0, 1e-9, 0)["kept"])
    assert picks[0]["s"].tolist() == [0, 1, 0, 0]
