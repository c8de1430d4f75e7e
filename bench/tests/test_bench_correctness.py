"""The correctness check on the CPU, at a size a test run holds.

A run of a training cell is driven end to end (set-up, window, reference,
comparison) with the look for a chip skipped, first as it is and then
with the timed step broken underneath: a step that returns its state
unchanged, one that leaves out half the batch and takes the mean over the
rest, a sparse backward that keeps the least important blocks, and one
that doubles every weight gradient. Each broken run must come out not
correct. So must the control and the planted faults of the reference put
in the program's place, held to each cell's limits
(``test_bench_controls.py``).
"""
from __future__ import annotations

import time

import pytest

from bench import run as bench_run

WORKLOAD = "resnet18-cifar10.dense"
SEED = 2**33 + 12345  # wider than 32 bits, as the driver's seeds are
SPARSE = "resnet18-cifar10.ssprop80"


def _run(root, workload=WORKLOAD):
    args = bench_run.parse(
        ["--workload", workload, "--seed", str(SEED), "--seconds", "1.2", "--trace", "0"]
    )
    return bench_run.execute(
        args, root=root, require_accelerator=False, t_start=time.perf_counter()
    )


def _unchanged_state(make):
    def builder(*a, **k):
        step = make(*a, **k)

        def broken(params, opt_state, images, labels):
            _, _, loss = step(params, opt_state, images, labels)
            return params, opt_state, loss

        return broken

    return builder


def _half_batch(make):
    def builder(*a, **k):
        step = make(*a, **k)

        def broken(params, opt_state, images, labels):
            half = images.shape[0] // 2
            return step(params, opt_state, images[:half], labels[:half])

        return broken

    return builder


@pytest.mark.parametrize("workload", [WORKLOAD, SPARSE])
def test_sound_run_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


@pytest.mark.parametrize(
    "fault, caught_by",
    [(_unchanged_state, "change_gap"), (_half_batch, "loss_gap")],
    ids=["state_unchanged", "half_batch"],
)
def test_broken_step_is_not_correct(tiny_root, monkeypatch, fault, caught_by):
    from repro.launch import steps

    monkeypatch.setattr(steps, "make_classifier_step", fault(steps.make_classifier_step))
    res = _run(tiny_root)
    assert not res["correct"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"]


def _lowest_blocks(monkeypatch):
    from repro.core import sparsity

    top = sparsity.select_topk_blocks
    monkeypatch.setattr(
        sparsity, "select_topk_blocks", lambda imp, *a, **k: top(-imp, *a, **k)
    )


def _doubled_weight_grad(monkeypatch):
    from repro.core import backward

    bwd = backward.channel_sparse_backward

    def doubled(*a, **k):
        dx, dw, db = bwd(*a, **k)
        return dx, 2 * dw, db

    monkeypatch.setattr(backward, "channel_sparse_backward", doubled)


@pytest.mark.parametrize(
    "plant, caught_by",
    [(_lowest_blocks, "selection_gap"), (_doubled_weight_grad, "grad_gap")],
    ids=["lowest_blocks", "doubled_weight_grad"],
)
def test_broken_sparse_backward_is_not_correct(tiny_root, monkeypatch, plant, caught_by):
    plant(monkeypatch)
    res = _run(tiny_root, SPARSE)
    assert not res["correct"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"]
