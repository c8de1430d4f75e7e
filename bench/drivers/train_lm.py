"""Driver: train a decoder LM through ``repro.launch.train``'s own objects.

Set-up builds what the training CLI builds (``train.build`` over the
CLI's own parser, with the configuration's ``program_args`` and the mix's
mesh, batch, sequence length and drop rate): the model, the host mesh,
the ``TokenPipeline`` from the seed and the policy program, whose
column-parallel sites select per shard of the mesh's ``model`` axis. It
makes the state with ``train.init_train_state`` (weights from the seed, Adam
at zero, placed by ``dist/sharding.py``), compiles ``Trainer.step_for``'s
jitted step (state donated), and drives it through the mix's first steps
with the window's own call and feed. Those steps give the readings that
the plain reference checks once the window has closed: each step's loss
and, per projection site (per shard where selection is balanced), which
blocks of weight-gradient columns it kept; each leaf's first gradient
norm, per layer, and the tied table's first gradient; each leaf's change
over the steps. A step's gradient is read from Adam's first moment,
``(m_t - b1 m_(t-1)) / (1 - b1)``: whole at the first step, where
``m_0`` is zero, and after it through a random sign sketch of each
kernel's rows, which keeps the output channels apart at a few floats per
channel, so no copy of the moments is held. The window then runs the
same object: every step copies its batch from host memory to the mesh,
dispatches the step and reads its loss, until ``seconds`` have passed.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.train_classifier import window
from bench.harness import cell as cell_lib
from bench.harness import compare, lm, phases, trace

TRACE_S = 10.0
SKETCH = 4  # random sign columns per kernel row sketch
SCOPES = ("attn_core", "lm_loss", "ssprop_tp", "ssprop_select", "ssprop_contract", "adam")
# the program's ModelConfig field of each published key of the configuration
PUBLISHED = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "param_dtype": "dtype",
    "remat": "remat", "scan_layers": "scan_layers",
}


def train_args(cell: cell_lib.Cell, seed: int) -> list[str]:
    """The training CLI's arguments for this cell."""
    cfg, mix = cell.config, cell.mix
    data, model = mix["mesh"]
    return [
        *cfg["program_args"],
        "--seq-len", str(mix["seq_len"]), "--global-batch", str(mix["global_batch"]),
        "--drop-rate", str(mix["drop_rate"]), "--granularity", mix["granularity"],
        "--scheduler", mix["schedule"], "--lr", str(cfg["optimizer"]["lr"]),
        "--data-mesh", str(data), "--model-mesh", str(model), "--seed", str(seed),
    ]


def trainer(cell: cell_lib.Cell, seed: int):
    """``train.build`` for this cell, checked against the configuration
    file and the mix: a program that runs anything else refuses."""
    from repro.launch import train

    t = train.build(train.build_parser().parse_args(train_args(cell, seed)))
    cfg, mix = cell.config, cell.mix
    runs = {k: getattr(t.cfg, f) for k, f in PUBLISHED.items()}
    states = {k: cfg[k] for k in PUBLISHED}
    o = cfg["optimizer"]
    runs["optimizer"] = (t.opt_cfg.b1, t.opt_cfg.b2, t.opt_cfg.eps, t.opt_cfg.clip_norm)
    states["optimizer"] = (o["b1"], o["b2"], o["eps"], o["clip_norm"])
    if runs != states or not t.cfg.qkv_bias:
        raise ValueError(f"the program runs {runs}, the configuration states {states}")
    table = t.resolved.policies_for_step(0)
    sel = selection(cell, t.mesh)
    for site, pol in table.entries:
        name = site.rsplit("/", 1)[1]
        got = (pol.drop_rate, pol.granularity, pol.block_size, pol.selection, max(pol.tp_shards, 1))
        want = (mix["drop_rate"], mix["granularity"], mix["block_size"], mix["selection"], sel[name][0])
        if got != want:
            raise ValueError(f"site {site} runs {got}, the mix states {want}")
    return t


def selection(cell: cell_lib.Cell, mesh) -> dict:
    ref = cell_lib.reference_module(cell)
    return ref.selection(cell.config, cell.mix, int(dict(mesh.shape).get("model", 1)))


# ----------------------------------------------------------------------
# readings of the program's state
# ----------------------------------------------------------------------
def _norm(a, axes):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axis=axes))


@functools.partial(jax.jit, static_argnums=(1,))
def _leaf_norms(tree, scale: float):
    """Norms of every leaf times ``scale``: per layer (``[L]``) for the
    stacked layers, whole for the rest."""
    return {
        k: jax.tree.map(
            lambda a: scale * _norm(a, tuple(range(1 if k == "stack" else 0, a.ndim))), v
        )
        for k, v in tree.items()
    }


@jax.jit
def _change_norms(p1, p0):
    return _leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p1, p0), 1.0)


def named(norms, n_layers: int) -> dict[str, float]:
    """``{leaf name: value}`` in the reference's names (``embed/table``,
    ``layer_3/attn/q/w``) from :func:`_leaf_norms`' tree."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]:
        parts = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if parts[0] == "stack":  # ['stack']['slots'][0][...], layers leading
            rest = "/".join(parts[3:])
            out.update({f"layer_{i}/{rest}": float(v[i]) for i in range(n_layers)})
        else:
            out["/".join(parts)] = float(v)
    return out


def _kernels(stack):
    """``{projection: [L, d_in, c]}`` of the (one-slot) stacked layers."""
    (slot,) = stack["slots"]
    return {n: slot[role][n]["w"] for role in ("attn", "mlp") for n in slot[role]}


@jax.jit
def _sketch(stack):
    """``{projection: [L, SKETCH, c]}``: each kernel's rows summed under
    fixed random signs, at float32 'highest'."""
    out = {}
    for n, a in _kernels(stack).items():
        r = jax.random.rademacher(jax.random.key(0), (a.shape[1], SKETCH), jnp.float32)
        out[n] = jnp.einsum("lic,ik->lkc", a, r, precision=jax.lax.Precision.HIGHEST)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _kept(now, prev, b1: float, sel: tuple):
    """``{projection: [L, shards, blocks]}`` norm of each block of a
    step's gradient columns, from the sketches after and before it."""
    out = {}
    for n, (shards, bs, _) in sel:
        g = (now[n] - b1 * prev[n]) / (1 - b1)
        cols = jnp.sum(jnp.square(g), axis=1)
        c = cols.shape[1]
        nb = -(-c // bs)
        cols = jnp.pad(cols, ((0, 0), (0, nb * bs - c)))
        out[n] = jnp.sqrt(cols.reshape(cols.shape[0], shards, nb // shards, bs).sum(-1))
    return out


def _by_key(cfg: dict, sel: dict, kept, ref) -> dict:
    kept = jax.device_get(kept)
    out = {}
    for key, (site, name, j, _) in ref.site_keys(cfg, sel).items():
        i = int(site.split("/")[0][len("layer_"):])
        out[key] = np.asarray(kept[name][i, j])
    return out


@dataclasses.dataclass
class Program:
    """The compiled step and its state, as the window drives it."""

    step: object
    params: object
    opt: object
    tokens: np.ndarray  # [R, B, S] in host memory
    targets: np.ndarray
    sharding: object
    next_batch: int = 0

    def feed(self):
        i = self.next_batch % self.tokens.shape[0]
        self.next_batch += 1
        return (
            jax.device_put(self.tokens[i], self.sharding),
            jax.device_put(self.targets[i], self.sharding),
        )

    def run_step(self, tokens, targets):
        batch = {"tokens": tokens, "targets": targets}
        self.params, self.opt, metrics = self.step(self.params, self.opt, batch)
        return metrics["loss"]


def batch_ring(pipe, ring: int) -> tuple[np.ndarray, np.ndarray]:
    """``ring`` batches of the trainer's ``TokenPipeline``, steps 0 on."""
    batches = [pipe.batch_at(i) for i in range(ring)]
    return (
        np.stack([b["tokens"] for b in batches]),
        np.stack([b["targets"] for b in batches]),
    )


def build(cell: cell_lib.Cell, seed: int):
    """Compile the step, make its state and traffic, run the first steps.

    Returns the trainer, the program, ready for the window, and its
    readings.
    """
    from repro.dist import sharding as shd
    from repro.launch import train

    cfg, mix = cell.config, cell.mix
    t = trainer(cell, seed)
    ref = cell_lib.reference_module(cell)
    sel = selection(cell, t.mesh)
    with jax.set_mesh(t.mesh):
        params, opt = train.init_train_state(t.cfg, t.mesh, t.seed)
    tokens, targets = batch_ring(t.pipe, mix["ring_batches"])
    sharding = shd.batch_shardings(t.mesh, jax.ShapeDtypeStruct(tokens.shape[1:], jnp.int32))
    prog = Program(None, params, opt, tokens, targets, sharding)
    x, y = prog.feed()
    prog.next_batch = 0
    with jax.set_mesh(t.mesh):
        prog.step = t.step_for(0).lower(params, opt, {"tokens": x, "targets": y}).compile()
    del x, y
    b1, n_layers = t.opt_cfg.b1, cfg["num_hidden_layers"]
    sel_t = tuple(sorted(sel.items()))
    losses, kept, first = [], [], None
    for i in range(mix["check_steps"]):
        prev = _sketch(prog.opt.m["stack"])  # zero before the first step
        losses.append(float(prog.run_step(*prog.feed())))
        now = _sketch(prog.opt.m["stack"])
        kept.append(_by_key(cfg, sel, _kept(now, prev, b1, sel_t), ref))
        if first is None:
            first = (
                named(_leaf_norms(prog.opt.m, 1.0 / (1 - b1)), n_layers),
                {"embed/table": np.asarray(jax.device_get(prog.opt.m["embed"]["table"])) / (1 - b1)},
            )
        del prev, now
    params0 = train.init_sharded_params(t.cfg, t.mesh, t.seed)
    readings = {
        "losses": losses,
        "grad_norms": first[0],
        "head_grads": first[1],
        "change_norms": named(_change_norms(prog.params, params0), n_layers),
        "kept": kept,
    }
    del params0
    return t, prog, readings


def reference_readings(cell: cell_lib.Cell, seed: int, mesh=None, **kw) -> dict:
    """The plain reference's readings over the cell's first batches, its
    weights from the key the program's come from (``PRNGKey(seed)``)."""
    from repro.data.pipeline import TokenPipeline, TokenPipelineConfig

    ref = cell_lib.reference_module(cell)
    cfg, mix = cell.config, cell.mix
    pipe = TokenPipeline(TokenPipelineConfig(cfg["vocab_size"], mix["seq_len"], mix["global_batch"], seed))
    batches = [(b["tokens"], b["targets"]) for b in map(pipe.batch_at, range(mix["check_steps"]))]
    return ref.readings(cfg, mix, jax.random.PRNGKey(seed), batches, mesh=mesh, **kw)


@dataclasses.dataclass(frozen=True)
class Observation:
    """What the per-layer readers read."""

    cell: cell_lib.Cell
    peaks: object
    steps: int
    reduction: trace.Reduction
    step_flops: float  # one chip's share of a step's required FLOPs
    phases: phases.Phases
    scope_s: dict
    collective_share: float | None


def layer_sites(cell: cell_lib.Cell) -> tuple[str, ...]:
    """The projection sites of one layer, as the scanned body names them."""
    ref = cell_lib.reference_module(cell)
    return tuple(f"{role}/{n}" for n, role in ref.PROJECTIONS.items())


def observe(ctx, trace_dir: str, steps: int, hlo: str, sel: dict) -> Observation:
    """Reduce the traced window; write ``phases.json`` beside it."""
    cell, cfg, mix = ctx.cell, ctx.cell.config, ctx.cell.mix
    tr = trace.load(trace_dir)
    leaves = lm.leaf_ops(tr)
    runs = phases.work(hlo)
    ph = phases.reduce(leaves, steps, runs, layer_sites(cell))
    scope_s = lm.scope_seconds(leaves, steps, runs, SCOPES)
    share = lm.collective_share(leaves, steps)
    table = ph.per_step_ms()
    table["scope_ms"] = {s: 1e3 * v / steps for s, v in scope_s.items()}
    table["collective_share"] = share
    with open(os.path.join(ctx.out_dir, "phases.json"), "w") as f:
        json.dump(table, f, indent=1)
    return Observation(
        cell=cell,
        peaks=ctx.peaks,
        steps=steps,
        reduction=trace.reduce(tr, steps),
        step_flops=lm.step_flops(cfg, mix["global_batch"], mix["seq_len"], sel) / cell.chips,
        phases=ph,
        scope_s=scope_s,
        collective_share=share,
    )


def run(ctx) -> dict:
    cell, seed = ctx.cell, ctx.seed
    mix = cell.mix
    t, prog, readings = build(cell, seed)
    mesh, sel = t.mesh, selection(cell, t.mesh)
    # A full collection scans every object that set-up left (JAX's traced
    # programs among them) and stalls the window; what set-up made is
    # moved out of the collector's reach.
    gc.collect()
    gc.freeze()
    trace_dir = os.path.join(ctx.out_dir, "trace")
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - ctx.t_start
    t0, ends, bad, traced = window(prog, ctx.seconds, TRACE_S if ctx.trace else 0.0)
    memory_peak = ctx.memory_peak()
    hlo = prog.step.as_text() if ctx.trace else ""
    del prog, t
    gc.collect()
    # drop the step's executables too, and the device memory they hold
    jax.clear_caches()

    ref = reference_readings(cell, seed, mesh=mesh, follow=compare.picks(readings["kept"]))
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
            # training sequences (examples) per second: tokens/s is
            # seq_len times it
            "train_images_per_s": n * mix["global_batch"] / (ends[-1] - t0) if n else 0.0,
            "setup_s": setup_s,
        }
        return out
    obs = observe(ctx, trace_dir, traced, hlo, sel)
    red = obs.reduction
    out["metrics"] = {name: reader.read(obs) for name, reader in cell.readers.items()}
    out["busy_s"] = red.busy_s
    out["window_s"] = red.window_s
    out["breakdown"] = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    return out
