"""Kernel-level before/after table for the paged-attention kernel.

* **paged attention** — the serving engine with the per-layer
  ``pool[block_tables]`` gather vs the in-place Pallas kernel.
  Asserted: token-for-token parity and the 3x->1x pool-bytes model.
* **micro parity cell** — the kernel against its gather oracle on one
  concrete small geometry, numerically (asserted) and wall-clock
  (informational: interpret-mode timings don't predict TPU).

Emits ``name,us_per_call,derived`` CSV like every table and writes
``BENCH_kernels.json`` next to this file.

Run:  PYTHONPATH=src python benchmarks/kernels_table.py [--smoke]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn

ATTN_ARCH = "qwen2.5-3b"


def attn_micro_row() -> dict:
    """Paged-attention kernel vs the gather+masked-attention reference
    on one small paged cache — max abs error asserted."""
    from repro.kernels import ops as kops

    key = jax.random.PRNGKey(1)
    b, s, h, kv, d = 3, 2, 4, 2, 8
    n_pages, bs_pg, nb = 10, 4, 3
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n_pages, bs_pg, kv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n_pages, bs_pg, kv, d), jnp.float32)
    tables = jax.random.randint(ks[3], (b, nb), 0, n_pages)
    qpos = jnp.array([[3, 4], [0, 1], [7, 8]], jnp.int32)

    out = kops.paged_attention(q, k_pool, v_pool, tables, qpos)

    # reference: materialize the gather, run masked attention per slot
    kg = k_pool[tables].reshape(b, nb * bs_pg, kv, d)
    vg = v_pool[tables].reshape(b, nb * bs_pg, kv, d)
    g = h // kv
    kk = jnp.repeat(kg, g, axis=2)
    vv = jnp.repeat(vg, g, axis=2)
    t = jnp.arange(nb * bs_pg)
    mask = t[None, None, :] <= qpos[:, :, None]
    scores = jnp.einsum("bshd,bthd->bhst", q, kk) / np.sqrt(d)
    scores = jnp.where(mask[:, None], scores, -1e30)
    ref = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), vv)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-4, f"paged attention diverged from gather oracle: {err}"
    us = time_fn(
        lambda: kops.paged_attention(q, k_pool, v_pool, tables, qpos),
        iters=3, warmup=1,
    )
    return {
        "kernel": "paged_attention",
        "shape": f"b{b}s{s}h{h}kv{kv}d{d}/pages{n_pages}x{bs_pg}",
        "max_err": err,
        "kernel_us": us,
    }


def run(json_path=None, smoke=False):
    from benchmarks import serve_latency

    rows = []
    arow = attn_micro_row()
    rows.append(arow)
    emit(
        f"kernels/{arow['kernel']}",
        arow["kernel_us"],
        f"max_err={arow['max_err']:.2e};gather parity OK",
    )
    if not smoke:
        srow = serve_latency.bench_attn_kernel(ATTN_ARCH)
        rows.append({"kernel": "paged_attention_engine", **srow})
        emit(
            f"kernels/paged_attention_engine/{srow['arch']}",
            srow["kernel_wall_s"] / max(srow["kernel_steps"], 1) * 1e6,
            f"kv_bytes/step {srow['kernel_kv_bytes_per_step']} vs gather"
            f" {srow['gather_kv_bytes_per_step']};token parity OK",
        )
    path = json_path or os.path.join(os.path.dirname(__file__), "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="skip the engine-level paged-attention A/B (CI)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(json_path=args.json, smoke=args.smoke)


if __name__ == "__main__":
    main()
