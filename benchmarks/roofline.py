"""Roofline analysis: three terms per (arch × shape × mesh), from the
dry-run artifacts + an honest-FLOPs probe.

Methodology (documented in EXPERIMENTS.md §Roofline):

* **Collective term** — parsed from the *production-mesh* compiled HLO
  (dry-run JSONs). Shapes there are per-device, so
  ``collective_t = wire_bytes_per_chip / link_bw``.
* **Compute / memory terms** — XLA's ``cost_analysis`` counts while-loop
  bodies (our layer scan, grad-accum scan, attention q-chunk scan) ONCE
  (verified empirically), so the production-mesh numbers undercount by
  the trip counts. We therefore compile a **probe**: the same step with
  layers UNROLLED (``scan_layers=False``), one microbatch (``accum=1``),
  unchunked attention, on a single device — every FLOP visible to XLA —
  and scale: ``total = probe_flops(one period-stack pass) × accum``;
  per-chip = total / chips (matmul FLOPs shard evenly; padding waste is
  a second-order effect noted per-cell). To bound probe compile time on
  the 88-95-layer models, we compile 1-period and 2-period variants and
  extrapolate linearly (periods are shape-identical, so the per-period
  delta is exact).
* **MODEL_FLOPS** = 6·N·D (train, N=active params, D=tokens/step),
  2·N·D (prefill), 2·N·B (decode).

Hardware: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.policy import SsPropPolicy, paper_default, tpu_default
from repro.data.pipeline import input_specs
from repro.launch import steps as steps_lib
from repro.models import transformer
from repro.optim import adam

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s / chip
LINK_BW = 50e9  # B/s / link
CHIPS = 256  # single-pod roofline basis

PROBE_DIR = os.path.join(os.path.dirname(__file__), "results", "probe")
DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def _probe_cfg(cfg, periods: int):
    plen = len(transformer.period_pattern(cfg))
    return dataclasses.replace(
        cfg,
        n_layers=plen * periods,
        n_enc_layers=min(cfg.n_enc_layers, periods) if cfg.n_enc_layers else 0,
        scan_layers=False,
        attn_q_chunk=1 << 30,
    )


def _probe_compile(cfg, shape, policy, accum):
    """Compile one unrolled variant on the host device; return cost dict."""
    if shape.kind == "train":
        micro = dataclasses.replace(shape, global_batch=max(1, shape.global_batch // accum))
        batch = input_specs(cfg, micro)
        fn = steps_lib.make_train_step(cfg, policy, adam.AdamConfig(lr=2e-4), accum=1)
        a_params, a_opt = steps_lib.abstract_state(cfg)
        lowered = jax.jit(fn).lower(a_params, a_opt, batch)
    elif shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        fn = steps_lib.make_prefill_step(cfg)
        a_params, _ = steps_lib.abstract_state(cfg)
        lowered = jax.jit(fn).lower(a_params, batch)
    else:
        a_params, _ = steps_lib.abstract_state(cfg)
        a_cache = steps_lib.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        state = {
            "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
            "cache": a_cache,
        }
        if cfg.family == "encdec":
            state["enc_out"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        fn = steps_lib.make_serve_step(cfg)
        lowered = jax.jit(fn).lower(a_params, state)
    c = lowered.compile().cost_analysis()
    return {"flops": float(c.get("flops", 0.0)),
            "bytes": float(c.get("bytes accessed", 0.0))}


def probe_cell(arch: str, shape_name: str, policy_name: str, cache=True):
    """Honest total-step FLOPs/bytes via 1- and 2-period extrapolation."""
    os.makedirs(PROBE_DIR, exist_ok=True)
    fname = os.path.join(PROBE_DIR, f"{arch}__{shape_name}__{policy_name}.json")
    if cache and os.path.exists(fname):
        with open(fname) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cfg.supports_shape(shape)
    if not ok:
        rec = {"status": "skipped", "why": why}
    else:
        import dataclasses as _dc
        if policy_name == "ssprop":
            policy = tpu_default(0.8)
        elif policy_name == "ssprop_tp":
            policy = _dc.replace(tpu_default(0.8), tp_shards=16)
        elif policy_name == "opt":
            policy = _dc.replace(
                tpu_default(0.8), tp_shards=16, bwd_dtype="bfloat16"
            )
        else:
            policy = SsPropPolicy(0.0)
        accum = steps_lib.microbatch_plan(cfg, shape, dp=16)
        np_full = transformer.n_periods(cfg)
        c1 = _probe_compile(_probe_cfg(cfg, 1), shape, policy, accum)
        c2 = _probe_compile(_probe_cfg(cfg, 2), shape, policy, accum)
        per_period = {k: c2[k] - c1[k] for k in c1}
        stack_pass = {k: c1[k] + (np_full - 1) * per_period[k] for k in c1}
        # enc-dec: encoder layers beyond the probe's 1-2 also extrapolate
        if cfg.n_enc_layers > 2:
            # encoder layer cost is inside per_period delta only when the
            # probe raised n_enc_layers with periods; our probe caps the
            # encoder at `periods`, so the same linear rule applies.
            pass
        total = {k: stack_pass[k] * (accum if shape.kind == "train" else 1) for k in c1}
        rec = {
            "status": "ok",
            "accum": accum,
            "n_periods": np_full,
            "probe_1": c1,
            "probe_2": c2,
            "total_flops": total["flops"],
            "total_bytes": total["bytes"],
        }
    rec.update({"arch": arch, "shape": shape_name, "policy": policy_name})
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def memory_model_bytes(cfg, shape, accum: int, chips: int = CHIPS) -> float:
    """Analytic HBM traffic per chip per step (fusion-aware lower model).

    ``cost_analysis()['bytes accessed']`` sums every HLO op's operands as
    if nothing fuses — a loose upper bound. This model counts the
    traffic a fused TPU executable actually pays: weight reads per
    microbatch, gradient/optimizer update traffic, activation
    save/restore at the remat boundaries, and KV/state cache traffic.
    Both numbers are reported; the §Roofline 'memory' term uses this one
    and the HLO number is kept as 'memory_hlo_s'.
    """
    p_bytes = cfg.param_count() * 2 / chips  # bf16 weights per chip
    if shape.kind == "train":
        tokens_chip = shape.seq_len * shape.global_batch / chips
        act = tokens_chip * cfg.d_model * cfg.n_layers * 2 * 6  # save+reread+recompute
        grads = 3 * p_bytes  # write + read + zero-init
        adam = cfg.param_count() * 4 * 4 / chips  # m,v read+write fp32
        return accum * p_bytes + grads + adam + act
    if shape.kind == "prefill":
        tokens_chip = shape.seq_len * shape.global_batch / chips
        return p_bytes + tokens_chip * cfg.d_model * cfg.n_layers * 2 * 2
    # decode: all weights + cache read/write per token
    if cfg.family == "ssm":
        cache = (
            shape.global_batch * cfg.n_ssm_heads * cfg.ssm_state * cfg.ssm_headdim
            * 4 * cfg.n_layers / chips
        )
    else:
        n_attn = (
            cfg.n_layers // cfg.attn_every if cfg.attn_every else cfg.n_layers
        )
        cache = (
            2 * shape.global_batch * shape.seq_len * cfg.n_kv_heads * cfg.head_dim
            * 2 * n_attn / chips
        )
        if cfg.attn_every:
            cache += (
                shape.global_batch * cfg.n_ssm_heads * cfg.ssm_state
                * cfg.ssm_headdim * 4
                * (cfg.n_layers - n_attn) / chips
            )
    # active weights only (MoE decode touches top-k + shared experts)
    p_active = cfg.active_param_count() * 2 / chips
    return p_active + 2 * cache


def model_flops(cfg, shape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch  # decode: one token per request


def lm_site_rows(arch, shape_name, policy_name="ssprop"):
    """Jaxpr-derived per-site projection FLOPs for one (arch, shape).

    Each row carries the plain forward cost and the *measured* backward
    contraction interval from tracing the site's actual backward program
    (``repro.analysis.savings``) — the per-site replacement for the 6ND
    ``model_flops`` estimate. The trailing ``lm_site_total`` row sums
    ``count * (fwd + bwd)`` and reports the ratio against 6ND so the
    aggregate drift of the estimate is visible per cell.
    """
    from repro.analysis import savings

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if policy_name in _CONV_POLICIES:
        policy = _CONV_POLICIES[policy_name]()
    else:  # the dryrun/probe policy names ("ssprop", "ssprop_tp", ...)
        policy = tpu_default(0.8)
    rows = []
    tot_fwd = tot_lo = tot_hi = 0
    for site, count, fwd, lo, hi in savings.lm_site_flops(
        cfg, policy, batch=shape.global_batch, seq=shape.seq_len
    ):
        tot_fwd += count * fwd
        tot_lo += count * lo
        tot_hi += count * hi
        rows.append({
            "arch": arch, "shape": shape_name, "policy": policy_name,
            "kind": "lm_site", "site": site, "count": count,
            "fwd_flops": fwd, "bwd_flops_lo": lo, "bwd_flops_hi": hi,
        })
    mf = model_flops(cfg, shape)
    mid = tot_fwd + (tot_lo + tot_hi) / 2
    rows.append({
        "arch": arch, "shape": shape_name, "policy": policy_name,
        "kind": "lm_site_total", "fwd_flops": tot_fwd,
        "bwd_flops_lo": tot_lo, "bwd_flops_hi": tot_hi,
        "model_flops_6nd": mf, "ratio_vs_6nd": mid / mf,
    })
    return rows


_CONV_POLICIES = {
    "dense": lambda: SsPropPolicy(0.0),
    "ssprop_channel": lambda: paper_default(0.8),
    "ssprop_block": lambda: tpu_default(0.8),
    "ssprop_block_pallas": lambda: dataclasses.replace(
        tpu_default(0.8), use_pallas=True
    ),
}
# the per-site policy-program row rides alongside the global policies
_CONV_POLICY_NAMES = tuple(_CONV_POLICIES) + ("ssprop_per_site",)

_CONV_CELLS = [
    # (model, batch, image) — paper Table 4/5 shapes
    ("resnet18", 128, (3, 32, 32)),
    ("resnet50", 128, (3, 32, 32)),
    ("ddpm", 128, (1, 32, 32)),
]


def _conv_policy(model: str, policy_name: str):
    """The policy (or resolved per-site table) for one conv row."""
    if policy_name != "ssprop_per_site":
        return _CONV_POLICIES[policy_name]()
    # A genuinely per-site program: stems/heads and the outermost blocks
    # dense (where gradient quality matters most per FLOP), everything
    # else at the paper's 0.8 — FLOPs are then summed over the resolved
    # site table, each conv at its own keep count.
    from repro.core.policy import PolicyProgram, PolicyRules
    from repro.models import ddpm, resnet

    base = paper_default(0.8)
    if model == "ddpm":
        sites, depth = ddpm.site_names()
        rules = PolicyRules.of(
            ("stem", 0.0), ("out", 0.0), ("mid*/*", 0.5), ("*", 0.8), base=base
        )
    else:
        sites, depth = resnet.site_names(model)
        rules = PolicyRules.of(
            ("stem", 0.0), ("block_{0,-1}/*", 0.0), ("*", 0.8), base=base
        )
    from repro.core.schedulers import Constant

    program = PolicyProgram(rules=rules, schedule=Constant(target=0.8))
    return program.resolve(sites, depth=depth).peak()


def _conv_flops(model: str, batch: int, image, policy):
    from repro.models import ddpm, resnet

    if model == "ddpm":
        return ddpm.flops_per_iter(batch, image, policy=policy)
    return resnet.flops_per_iter(model, batch, image, policy=policy)


def _conv_sites(model: str, image):
    from repro.models import ddpm, resnet

    if model == "ddpm":
        return ddpm.iter_conv_shapes(image)
    return resnet.iter_conv_shapes(model, image)


def _conv_bytes(model: str, batch: int, image, policy) -> int:
    """Whole-model backward HBM traffic (conv_backward_bytes_policy)."""
    from repro.core import flops as F

    return sum(
        F.conv_backward_bytes_site(batch, h, w, ci, co, k, policy, site)
        for site, ci, co, k, h, w in _conv_sites(model, image)
    )


@functools.lru_cache(maxsize=None)
def _conv_param_bytes(model: str, image) -> float:
    from repro.models import ddpm, resnet

    if model == "ddpm":
        shapes = jax.eval_shape(
            lambda k: ddpm.init_params(k, channels=image[0]), jax.random.PRNGKey(0)
        )
    else:
        shapes = jax.eval_shape(
            lambda k: resnet.init_params(model, k, in_channels=image[0]),
            jax.random.PRNGKey(0),
        )
    return float(
        sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    )


def conv_roofline_row(model: str, batch: int, image, policy_name: str):
    """Backward-pass roofline terms for a conv model under one policy.

    Compute comes from the policy-aware Eq. 6/9 model
    (``conv_backward_flops_policy``): block granularity counts whole
    kept blocks, so the block rows reflect what the unified backward
    engine executes, not the nominal channel top-k rate. The memory term
    is the policy-aware bytes-moved model
    (``conv_backward_bytes_policy`` summed over the model's conv sites)
    plus the weights traffic (grad write + read + param read) — the
    bytes column rides next to the FLOPs columns so compute- vs
    memory-bound is read off the same row.
    """
    policy = _conv_policy(model, policy_name)
    dense_f, policy_f = _conv_flops(model, batch, image, policy)
    p_bytes = _conv_param_bytes(model, image)
    bytes_moved = _conv_bytes(model, batch, image, policy)
    compute_t = policy_f / PEAK_FLOPS
    memory_t = (bytes_moved + 3 * p_bytes) / HBM_BW
    return {
        "arch": model,
        "shape": f"b{batch}x{image[1]}",
        "policy": policy_name,
        "status": "ok",
        "compute_s": compute_t,
        "memory_s": memory_t,
        "dominant": "compute" if compute_t >= memory_t else "memory",
        "dense_flops": dense_f,
        "policy_flops": policy_f,
        "bytes_moved": bytes_moved,
        "saved": 1.0 - policy_f / dense_f,
    }


def iter_conv_rows():
    """All (model × policy) conv roofline rows — shared by run()/main()."""
    for model, batch, image in _CONV_CELLS:
        for pname in _CONV_POLICY_NAMES:
            yield conv_roofline_row(model, batch, image, pname)


def _load_dryrun(arch, shape_name, mesh, policy):
    f = os.path.join(DRYRUN_DIR, f"{arch}__{shape_name}__{mesh}__{policy}.json")
    if not os.path.exists(f):
        return None
    with open(f) as fh:
        return json.load(fh)


def roofline_row(arch, shape_name, policy="ssprop", mesh="single"):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    dr = _load_dryrun(arch, shape_name, mesh, policy)
    pr = probe_cell(arch, shape_name, policy)
    if pr.get("status") != "ok" or dr is None or dr.get("status") != "ok":
        return {
            "arch": arch, "shape": shape_name, "policy": policy,
            "status": pr.get("why") or (dr or {}).get("status", "missing"),
        }
    chips = dr["devices"]
    compute_t = pr["total_flops"] / chips / PEAK_FLOPS
    memory_hlo_t = pr["total_bytes"] / chips / HBM_BW
    memory_t = memory_model_bytes(cfg, shape, pr.get("accum", 1), chips) / HBM_BW
    coll_t = dr["collective_wire_bytes"] / LINK_BW  # already per-chip
    mf = model_flops(cfg, shape)
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    frac = compute_t / bound if bound > 0 else 0.0
    return {
        "arch": arch,
        "shape": shape_name,
        "policy": policy,
        "status": "ok",
        "compute_s": compute_t,
        "memory_s": memory_t,
        "memory_hlo_s": memory_hlo_t,
        "collective_s": coll_t,
        "dominant": dom,
        "roofline_fraction": frac,  # compute / dominant: 1.0 == compute-bound
        "model_flops": mf,
        "hlo_flops": pr["total_flops"],
        "useful_ratio": mf / pr["total_flops"] if pr["total_flops"] else 0.0,
    }


def run():
    """Benchmark-harness entry: emit roofline rows for available cells."""
    from benchmarks.common import emit

    for arch in ARCH_IDS:
        for shape_name in SHAPES:
            row = roofline_row(arch, shape_name)
            if row.get("status") != "ok":
                emit(f"roofline/{arch}/{shape_name}", 0.0, f"status={row['status']}")
                continue
            emit(
                f"roofline/{arch}/{shape_name}",
                row["compute_s"] * 1e6,
                f"dom={row['dominant']};frac={row['roofline_fraction']:.3f};"
                f"mem_s={row['memory_s']:.4f};coll_s={row['collective_s']:.4f};"
                f"useful={row['useful_ratio']:.3f}",
            )
    # conv rows: the op the paper is about, through the policy-aware
    # FLOPs model (channel vs block vs block+Pallas keep counts).
    for row in iter_conv_rows():
        emit(
            f"roofline/conv/{row['arch']}/{row['policy']}",
            row["compute_s"] * 1e6,
            f"dom={row['dominant']};saved={row['saved']:.3f};"
            f"mem_s={row['memory_s']:.4f};bytes={row['bytes_moved']}",
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--policy", default="ssprop")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--conv", action="store_true",
                    help="emit the conv-model rows (policy-aware FLOPs)")
    ap.add_argument("--lm-sites", action="store_true",
                    help="emit jaxpr-derived per-site projection rows "
                    "(measured backward interval, replacing the 6ND "
                    "estimate) for the selected cell(s)")
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    rows = []
    if args.lm_sites:
        cells = (
            [(a, s) for a in ARCH_IDS for s in SHAPES]
            if args.all
            else [(args.arch, args.shape)]
        )
        for a, s in cells:
            for row in lm_site_rows(a, s, args.policy):
                rows.append(row)
                if row["kind"] == "lm_site":
                    print(
                        f"{a:28s} {s:12s} {row['site']:24s} "
                        f"x{row['count']:<3d} fwd={row['fwd_flops']:.3e} "
                        f"bwd=[{row['bwd_flops_lo']:.3e}, "
                        f"{row['bwd_flops_hi']:.3e}]"
                    )
                else:
                    print(
                        f"{a:28s} {s:12s} {'TOTAL':24s}      "
                        f"fwd={row['fwd_flops']:.3e} "
                        f"bwd=[{row['bwd_flops_lo']:.3e}, "
                        f"{row['bwd_flops_hi']:.3e}] "
                        f"6ND={row['model_flops_6nd']:.3e} "
                        f"ratio={row['ratio_vs_6nd']:.3f}"
                    )
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return
    if args.conv:
        for row in iter_conv_rows():
            rows.append(row)
            print(
                f"{row['arch']:10s} {row['shape']:8s} {row['policy']:20s} "
                f"comp={row['compute_s']:.4f}s mem={row['memory_s']:.4f}s "
                f"bytes={row['bytes_moved']/1e9:.2f}GB "
                f"saved={row['saved']:.3f} dom={row['dominant']}"
            )
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=1)
        return
    cells = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    for a, s in cells:
        row = roofline_row(a, s, policy=args.policy)
        rows.append(row)
        if row.get("status") == "ok":
            print(
                f"{a:28s} {s:12s} comp={row['compute_s']:.4f}s "
                f"mem={row['memory_s']:.4f}s coll={row['collective_s']:.4f}s "
                f"dom={row['dominant']:10s} frac={row['roofline_fraction']:.3f} "
                f"useful={row['useful_ratio']:.2f}"
            )
        else:
            print(f"{a:28s} {s:12s} -- {row['status']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
