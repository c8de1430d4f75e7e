import os

# Before any other import: jax fixes its platform and the host device
# count at first init. The dry run lowers on placeholder CPU devices, so
# it pins the CPU even on a TPU host (where the chip belongs to whichever
# process claims it first) and adds its device-count flag to the caller's
# XLA_FLAGS rather than replacing them. REPRO_DRYRUN_DEVICES shrinks the
# placeholder pool for tests.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    filter(None, (
        os.environ.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512"),
    ))
)

"""Multi-pod dry-run driver.

For every (architecture × input-shape × mesh) cell: build abstract
(ShapeDtypeStruct) params / optimizer state / inputs with production
shardings, ``jit(step).lower(...).compile()`` against the 16×16 (256
chips) or 2×16×16 (512 chips) mesh, and record
``memory_analysis()`` / ``cost_analysis()`` / the collective schedule
parsed from the compiled HLO. No tensor is ever allocated.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single --policy ssprop
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.policy import DENSE, PolicyProgram, tpu_default
from repro.data.pipeline import input_specs
from repro.dist import sharding as shd
from repro.launch import steps as steps_lib
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.optim import adam

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str, loop_mults=None):
    """Per-device collective bytes by op kind, from compiled (SPMD) HLO.

    Result shapes in the partitioned module are per-device. Wire-cost
    factors: ring all-reduce sends+receives ≈ 2x the shard bytes;
    all-gather/reduce-scatter/all-to-all/permute ≈ 1x.

    ``loop_mults``: per-loop-depth trip multipliers. HLO text lists a
    while body ONCE; an op whose op_name metadata sits N ``while/body``
    frames deep executes ``loop_mults[N]`` times per step (train:
    [1, accum, accum*n_periods, ...]). Without this the wire bytes of
    scanned layers are undercounted by up to ~1000x on the big models.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line[: m.start()]:
            continue
        kind = m.group(1)
        lhs = line.split(" = ", 1)
        shapes = _SHAPE_RE.findall(lhs[1][: m.start() - len(lhs[0]) - 3] if len(lhs) > 1 else line[: m.start()])
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        mult = 1
        if loop_mults:
            depth = line.count("while/body")
            mult = loop_mults[min(depth, len(loop_mults) - 1)]
        rec = out.setdefault(kind, {"count": 0, "bytes": 0, "stepped_bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["stepped_bytes"] += int(nbytes * mult)
    factor = {"all-reduce": 2.0}
    wire = sum(
        v.get("stepped_bytes", v["bytes"]) * factor.get(k, 1.0)
        for k, v in out.items()
    )
    return out, int(wire)


def _sds(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree,
        shardings,
    )


def build_cell(arch: str, shape_name: str, mesh, policy_name: str):
    """Returns (fn, example_args_as_sds, meta) for one dry-run cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cfg.supports_shape(shape)
    if not ok:
        return None, None, {"skipped": why}

    import dataclasses as _dc

    dp = 1
    for a in dp_axes(mesh):
        dp *= mesh.shape[a]

    if policy_name == "ssprop":
        policy = tpu_default(0.8)
    elif policy_name == "ssprop_tp":
        # §Perf iteration 1: TP-local per-shard top-k (comm-free gather)
        policy = _dc.replace(tpu_default(0.8), tp_shards=int(mesh.shape["model"]))
    elif policy_name == "opt":
        # §Perf combined: TP-local selection + DP-local MoE dispatch +
        # seq-sharded decode + bf16 backward + donated decode state
        # (see EXPERIMENTS.md §Perf iterations 1-5)
        policy = _dc.replace(
            tpu_default(0.8),
            tp_shards=int(mesh.shape["model"]),
            bwd_dtype="bfloat16",
        )
        cfg = _dc.replace(cfg, moe_dp_groups=dp, decode_seq_shard=True)
    elif policy_name == "dense":
        policy = DENSE
    else:
        raise ValueError(policy_name)

    # The cell's control surface is a (trivial one-rule) policy program;
    # the compiled step consumes its resolved site table — the same
    # object a per-site program would hand the train loop.
    from repro.models import model as _lm

    sites, depth = _lm.site_names(cfg)
    policy = PolicyProgram.single(policy).resolve(sites, depth=depth).peak()

    a_params, a_opt = steps_lib.abstract_state(cfg)
    p_sh = shd.param_shardings(mesh, a_params, replicate_kv=(policy_name == "opt"))
    params_sds = _sds(a_params, p_sh)

    from repro.models import transformer as _tf

    np_ = _tf.n_periods(cfg) if cfg.family != "encdec" else cfg.n_layers
    chunks = max(1, shape.seq_len // 1024)
    meta = {
        "arch": arch,
        "shape": shape_name,
        "policy": policy_name,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "n_periods": np_,
    }

    if shape.kind == "train":
        accum = steps_lib.microbatch_plan(cfg, shape, dp)
        meta["accum"] = accum
        meta["loop_mults"] = [1, accum, accum * np_, accum * np_ * chunks]
        opt_sh = shd.opt_state_shardings(mesh, a_params)
        opt_sds = adam.AdamState(
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=shd.replicated(mesh)),
            m=_sds(a_opt.m, opt_sh),
            v=_sds(a_opt.v, opt_sh),
        )
        batch = input_specs(cfg, shape)
        batch_sds = _sds(batch, shd.batch_shardings(mesh, batch))
        opt_cfg = adam.AdamConfig(lr=2e-4, clip_norm=1.0)
        fn = steps_lib.make_train_step(cfg, policy, opt_cfg, accum=accum)
        return fn, (params_sds, opt_sds, batch_sds), meta

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        batch_sds = _sds(batch, shd.batch_shardings(mesh, batch))
        fn = steps_lib.make_prefill_step(cfg)
        meta["loop_mults"] = [1, np_, np_ * chunks]
        return fn, (params_sds, batch_sds), meta

    # decode
    b = shape.global_batch
    a_cache = steps_lib.abstract_cache(cfg, b, shape.seq_len)
    cache_sds = _sds(
        a_cache,
        shd.cache_shardings(mesh, a_cache, seq_shard=(policy_name == "opt")),
    )
    dpax = dp_axes(mesh)
    baxis = dpax if len(dpax) > 1 else (dpax[0] if dpax else None)
    state = {
        "tokens": jax.ShapeDtypeStruct(
            (b, 1),
            jnp.int32,
            sharding=NamedSharding(mesh, shd.fit_spec(P(baxis, None), (b, 1), mesh)),
        ),
        "pos": jax.ShapeDtypeStruct((), jnp.int32, sharding=shd.replicated(mesh)),
        "cache": cache_sds,
    }
    if cfg.family == "encdec":
        state["enc_out"] = jax.ShapeDtypeStruct(
            (b, cfg.enc_seq, cfg.d_model),
            jnp.dtype(cfg.dtype),
            sharding=NamedSharding(mesh, P(baxis, None, None)),
        )
    fn = steps_lib.make_serve_step(cfg)
    meta["decode"] = True
    meta["loop_mults"] = [1, np_, np_]
    return fn, (params_sds, state), meta


def _placement_report(args_sds) -> dict:
    """Input placements of one cell, by pytree path — the cheap audit
    surface for the sharding rule table (``--placements-only``): the
    first tree is the params (reported as a spec → leaf-count
    histogram), the rest (batch / opt state / serve state) leaf by
    leaf. No lowering, no compile."""
    params, *rest = args_sds
    hist: dict = {}
    for _, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        k = str(leaf.sharding.spec)
        hist[k] = hist.get(k, 0) + 1
    inputs = {}
    for tree in rest:
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: hasattr(x, "sharding")
        )[0]:
            if hasattr(leaf, "sharding"):
                inputs[jax.tree_util.keystr(path)] = str(leaf.sharding.spec)
    return {"param_spec_histogram": hist, "inputs": inputs}


def run_cell(
    arch, shape_name, mesh_kind, policy_name, out_dir=None, verbose=True,
    placements_only=False,
):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    fn, args, meta = build_cell(arch, shape_name, mesh, policy_name)
    rec = dict(meta, mesh=mesh_kind, devices=mesh.devices.size)
    if fn is None:
        rec["status"] = "skipped"
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: SKIP ({meta['skipped']})")
        return rec
    if placements_only:
        rec["placements"] = _placement_report(args)
        rec["status"] = "ok"
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {mesh_kind} × {policy_name}: placements")
            for k, v in rec["placements"]["inputs"].items():
                print(f"  {k}: {v}")
        print(json.dumps(rec["placements"]))
        return rec
    try:
        with mesh:
            donate = (0, 1) if meta.get("accum") else ()
            if meta.get("decode") and policy_name == "opt":
                donate = (1,)  # donate the serving state (cache) buffers
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            try:
                mem = compiled.memory_analysis()
                rec["memory"] = {
                    "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                    "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                    "generated_code_bytes": int(
                        getattr(mem, "generated_code_size_in_bytes", 0)
                    ),
                }
            except Exception as e:  # pragma: no cover
                rec["memory"] = {"error": str(e)}
            try:
                cost = compiled.cost_analysis()
                rec["cost"] = {
                    "flops": float(cost.get("flops", -1)),
                    "bytes_accessed": float(cost.get("bytes accessed", -1)),
                }
            except Exception as e:  # pragma: no cover
                rec["cost"] = {"error": str(e)}
            hlo = compiled.as_text()
            colls, wire = parse_collectives(hlo, meta.get("loop_mults"))
            rec["collectives"] = colls
            rec["collective_wire_bytes"] = wire
            rec["status"] = "ok"
            rec["lower_s"] = round(t_lower, 2)
            rec["compile_s"] = round(t_compile, 2)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    if verbose:
        s = rec["status"]
        extra = ""
        if s == "ok":
            mb = rec["memory"].get("argument_bytes", 0) / mesh.devices.size / 2**30
            extra = (
                f" flops/dev={rec['cost'].get('flops', 0):.3e}"
                f" args/dev={mb:.2f}GiB wire/dev={rec['collective_wire_bytes']/2**30:.3f}GiB"
                f" (lower {rec['lower_s']}s compile {rec['compile_s']}s)"
            )
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind} × {policy_name}: {s}{extra}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_kind}__{policy_name}.json"
        rec.pop("traceback", None) if rec["status"] == "ok" else None
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--policy", choices=["ssprop", "ssprop_tp", "opt", "dense"], default="ssprop")
    ap.add_argument("--all", action="store_true", help="every (arch × shape)")
    ap.add_argument("--placements-only", action="store_true",
                    help="report input placements (JSON) without "
                         "lowering/compiling — fast rule-table audit")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = 0
    for a, s in cells:
        rec = run_cell(
            a, s, args.mesh, args.policy,
            out_dir=None if args.placements_only else args.out,
            placements_only=args.placements_only,
        )
        if rec["status"] == "error":
            failures += 1
            print(rec.get("error"))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
