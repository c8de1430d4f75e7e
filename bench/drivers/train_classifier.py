"""Driver: train a classifier through the program's own step builder.

Set-up builds one object, the compiled ``steps.make_classifier_step``
executable with its state (weights from ``resnet.init_params`` and Adam's
state, made from the seed in one jitted call), and drives it through the
mix's first steps with the window's own call and feed. Those steps give
the readings that the plain reference checks once the window has closed:
each step's loss and, per conv site, which blocks of weight-gradient rows
it kept; each leaf's first gradient norm and the head's first gradient;
each leaf's change over the steps. A step's gradient is read from Adam's
first moment, ``(m_t - b1 m_(t-1)) / (1 - b1)``. The window then runs the
same object: every step copies its batch from host memory, dispatches the
step and reads its loss, until ``seconds`` have passed.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import cell as cell_lib
from bench.harness import compare, data, flops, trace

# Names of the Pallas kernels of the sparse backward (the program's
# kernels/gathered_matmul.py): the wrappers and their kernel bodies.
SPARSE_BWD_KERNELS = (
    "conv_dw_fused", "conv_dx_fused", "dx_gathered", "dw_gathered",
    "_conv_dw_kernel", "_conv_dx_kernel", "_dx_kernel", "_dw_kernel",
)
# The profiler traces the window's first seconds only: the device's trace
# buffer holds about a million operations, and a 40 s window of 8 ms CIFAR
# steps (about 315 operations each) overflowed it and dropped the rest.
TRACE_S = 10.0
PROGRAM_STEM = {"kernel": 3, "stride": 1, "maxpool": False}


def _policy(mix: dict):
    from repro.core.policy import tpu_default

    if mix["policy"] != "tpu_default":
        raise ValueError(f"unknown policy {mix['policy']!r}")
    pol = dataclasses.replace(tpu_default(mix["drop_rate"]), use_pallas=mix["use_pallas"])
    stated = (mix["granularity"], mix["block_size"], mix["selection"])
    if (pol.granularity, pol.block_size, pol.selection) != stated:
        raise ValueError(f"policy {pol} is not the mix's {stated}")
    return pol


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@jax.jit
def _change_norms(p1, p0):
    return _leaf_norms(jax.tree.map(jnp.subtract, p1, p0))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _grad_reading(m, m_prev, b1, sites, block, block_norms):
    """A step's gradient, from Adam's first moment before and after it:
    its leaf norms, the head's gradient and the conv sites' block norms."""
    g = jax.tree.map(lambda a, b: (a - b1 * b) / (1 - b1), m, m_prev)
    return _leaf_norms(g), g["head"], block_norms(g, dict(sites), block)


@jax.jit
def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _flat(tree) -> dict[str, float]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): float(v) for p, v in leaves}


def _flat_arrays(tree) -> dict[str, np.ndarray]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in leaves}


@dataclasses.dataclass
class Program:
    """The compiled step and its state, as the window drives it."""

    step: object
    params: object
    opt: object
    images: np.ndarray  # [R, B, C, H, W] in host memory
    labels: np.ndarray
    device: object
    next_batch: int = 0

    def feed(self):
        i = self.next_batch % self.images.shape[0]
        self.next_batch += 1
        return (
            jax.device_put(self.images[i], self.device),
            jax.device_put(self.labels[i], self.device),
        )

    def run_step(self, x, y):
        self.params, self.opt, loss = self.step(self.params, self.opt, x, y)
        return loss


def build(cell: cell_lib.Cell, seed: int, step=None) -> tuple[Program, dict]:
    """Compile the step, make its state and traffic, run the first steps.

    Returns the program, ready for the window, and its readings. ``step``
    reuses an executable that an earlier call compiled for this cell.
    """
    from repro.launch import steps
    from repro.models import resnet
    from repro.optim import adam

    cfg, mix = cell.config, cell.mix
    if cfg["stem"] != PROGRAM_STEM or cfg["layout"] != "basic":
        raise ValueError("the classifier step runs the 3x3/s1 stem of a basic-block ResNet")
    o = cfg["optimizer"]
    opt_cfg = adam.AdamConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
    policy = _policy(mix)

    def init(key):
        p = resnet.init_params(cfg["model"], key, cfg["n_classes"], cfg["image"][0])
        return p, adam.init(p)

    device = jax.devices()[0]
    params, opt = jax.jit(init)(data.param_key(seed))
    images, labels = data.batch_ring(
        seed, ring=mix["ring_batches"], batch=cfg["batch"],
        image=cfg["image"], n_classes=cfg["n_classes"],
    )
    prog = Program(step, params, opt, images, labels, device)
    if step is None:
        x, y = prog.feed()
        prog.next_batch = 0
        # params and optimizer state are donated, as launch/train.py does
        prog.step = (
            jax.jit(
                steps.make_classifier_step(cfg["model"], policy, opt_cfg),
                donate_argnums=(0, 1),
            )
            .lower(params, opt, x, y)
            .compile()
        )
        del x, y
    ref = cell_lib.reference_module(cell)
    sites = tuple(ref.conv_sites(cfg).items())
    block = 1 if mix["granularity"] == "channel" else mix["block_size"]
    # each reading is taken before the next step donates what it reads
    params0, losses, kept, first = _copy(prog.params), [], [], None
    for _ in range(mix["check_steps"]):
        m_prev = _copy(prog.opt.m)
        losses.append(float(prog.run_step(*prog.feed())))
        gn, head, kn = _grad_reading(prog.opt.m, m_prev, o["b1"], sites, block, ref.block_norms)
        del m_prev
        kept.append(jax.device_get(kn))
        first = first or (_flat(gn), {f"['head']{k}": v for k, v in _flat_arrays(head).items()})
    readings = {
        "losses": losses,
        "grad_norms": first[0],
        "head_grads": first[1],
        "change_norms": _flat(_change_norms(prog.params, params0)),
        "kept": kept,
    }
    del params0
    return prog, readings


def window(prog: Program, seconds: float, trace_s: float = 0.0):
    """Drive the step for ``seconds``.

    Returns the start, each counted step's end, the number of bad losses
    and the number of steps inside the trace: with ``trace_s`` the
    profiler, started by the caller, stops after the step that ends past
    ``trace_s``.
    """
    annotate = jax.profiler.TraceAnnotation
    ends, bad, traced = [], 0, 0
    tracing = trace_s > 0
    t0 = time.perf_counter()
    stop = t0 + seconds
    while True:
        if tracing and time.perf_counter() - t0 >= trace_s:
            jax.profiler.stop_trace()
            tracing, traced = False, len(ends)
        with annotate(trace.STEP_SPAN):
            with annotate("bench/batch_transfer"):
                x, y = prog.feed()
            with annotate("bench/dispatch"):
                loss = prog.run_step(x, y)
            with annotate("bench/loss_read"):
                lv = float(loss)
        t = time.perf_counter()
        if t > stop:
            if tracing:
                jax.profiler.stop_trace()
                traced = len(ends)
            return t0, ends, bad, traced
        ends.append(t)
        bad += not math.isfinite(lv)


def reference_readings(cell: cell_lib.Cell, seed: int, **kw) -> dict:
    ref = cell_lib.reference_module(cell)
    cfg, mix = cell.config, cell.mix
    images, labels = data.batch_ring(
        seed, ring=mix["ring_batches"], batch=cfg["batch"],
        image=cfg["image"], n_classes=cfg["n_classes"],
    )
    batches = [(images[i], labels[i]) for i in range(mix["check_steps"])]
    return ref.readings(cfg, mix, data.param_key(seed), batches, **kw)


@dataclasses.dataclass(frozen=True)
class Observation:
    """What the per-layer readers read."""

    cell: cell_lib.Cell
    peaks: object
    steps: int
    reduction: trace.Reduction
    step_flops: float
    kernel_least_s: float  # per step


def run(ctx) -> dict:
    cell, seed = ctx.cell, ctx.seed
    cfg, mix = cell.config, cell.mix
    prog, readings = build(cell, seed)
    # A full collection scans every object that set-up left (JAX's traced
    # programs among them) and stalled single windows for 0.5-2.8 s; what
    # set-up made is moved out of the collector's reach.
    gc.collect()
    gc.freeze()
    trace_dir = os.path.join(ctx.out_dir, "trace")
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - ctx.t_start
    t0, ends, bad, traced = window(prog, ctx.seconds, TRACE_S if ctx.trace else 0.0)
    memory_peak = ctx.memory_peak()
    del prog
    gc.collect()

    ref = reference_readings(cell, seed, follow=compare.picks(readings["kept"]))
    checks = compare.checks(compare.gaps(readings, ref, cell.limits), cell.limits)
    n = len(ends)
    out = {
        "correct": compare.passed(checks) and bad == 0 and n > 0,
        "attempted": n,
        "failed": bad,
        "memory_peak_bytes": memory_peak,
        "checks": checks,
    }
    with open(os.path.join(ctx.out_dir, "steps.json"), "w") as f:
        json.dump({"t0": t0, "ends": ends, "trace": ctx.trace}, f)
    if not ctx.trace:
        out["metrics"] = {
            "train_images_per_s": n * cfg["batch"] / (ends[-1] - t0) if n else 0.0,
            "setup_s": setup_s,
        }
        return out
    red = trace.reduce(trace.load(trace_dir), traced, SPARSE_BWD_KERNELS)
    pol = {k: mix[k] for k in ("drop_rate", "granularity", "block_size")}
    calls = flops.sparse_kernel_calls(cfg, cfg["batch"], pol) if pol["drop_rate"] > 0 else []
    obs = Observation(
        cell=cell,
        peaks=ctx.peaks,
        steps=traced,
        reduction=red,
        step_flops=flops.step_flops(cfg, cfg["batch"], pol),
        kernel_least_s=sum(
            c.least_s(ctx.peaks.bf16_flops, ctx.peaks.hbm_bytes_per_s) for c in calls
        ),
    )
    out["metrics"] = {name: reader.read(obs) for name, reader in cell.readers.items()}
    out["busy_s"] = red.busy_s
    out["window_s"] = red.window_s
    out["breakdown"] = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    return out
