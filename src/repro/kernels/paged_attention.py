"""Pallas paged-attention decode kernel: K/V pages read in place.

The serving gather this kernel kills (``models/layers.py::attn_apply``,
paged branch) rebuilds a contiguous ``[B, NB*bs, KV, hd]`` K/V view from
the page pool **every layer** — ``pool[block_tables].reshape(...)`` is a
full HBM copy of the cache just to feed ``masked_attention``. Here the
block table rides in SMEM (scalar prefetch) and the ``BlockSpec`` index
map addresses physical page ``tables[b, j]`` directly during the
HBM→VMEM copy of grid step ``(b, j)`` — vLLM-style paged attention; the
pool is never re-materialized.

Addressing rules (mirrors the write path in ``attn_apply``):
  * grid = (B, NB): batch row × *logical* block; the K/V index map reads
    physical page ``tables[b*NB + j]``, so the tokens seen at step j sit
    at logical positions ``j*bs + [0, bs)``.
  * per-slot causality: a key at logical position t attends query row s
    iff ``t <= qpos[b, s]`` — identical to the gather path's mask, so
    stale pages of a slot's previous occupant and unassigned table
    entries (page 0) are fenced exactly as before.
  * softmax is *online* (flash-style running max/denominator in VMEM
    scratch) since pages stream block-by-block; all accumulation fp32.
    Masked positions contribute exp-of-masked = 0 explicitly — an
    all-masked page must not inflate the denominator.

GQA: q heads fold into their KV group (``[KV, S*G, D]``, row ``s*G + g``)
so the batched dot contracts per KV head without materializing the
repeated K/V the einsum path uses. The wrapper builds that layout, and
the per-row query positions ``[S*G, 1]``, before the call.
"""
from __future__ import annotations

import functools
import math

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from repro.kernels import specs
from repro.kernels.gathered_matmul import mxu_dot


def _paged_attn_kernel(
    tbl_ref, q_ref, k_ref, v_ref, qpos_ref, o_ref, m_ref, l_ref, acc_ref,
    *, bs_pg: int, nb: int, scale: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qg = q_ref[0]  # [KV, SG, D] — row r of KV head kv is q head kv*G + r%G
    kvh, sg, _ = qg.shape
    k = k_ref[0].transpose(1, 0, 2)  # [KV, bs, D] — physical page tbl[b, j]
    v = v_ref[0].transpose(1, 0, 2).astype(jnp.float32)
    scores = mxu_dot(qg, k, (((2,), (2,)), ((0,), (0,)))) * scale  # [KV, SG, bs]

    # logical positions of this page's tokens vs per-row query positions
    t_pos = j * bs_pg + jax.lax.broadcasted_iota(jnp.int32, (sg, bs_pg), 1)
    mask = t_pos <= qpos_ref[0]  # [SG, bs] against [SG, 1]

    m_prev = m_ref[...]  # [KV, SG]
    s_max = jnp.max(jnp.where(mask[None], scores, -1e30), axis=-1)
    m_new = jnp.maximum(m_prev, s_max)
    # exp(-1e30 - (-1e30)) = 1: masked slots must be zeroed explicitly,
    # not left to the exp — an all-masked page would corrupt l otherwise.
    p = jnp.where(mask[None], jnp.exp(scores - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + mxu_dot(
        p, v, (((2,), (1,)), ((0,), (0,)))
    )
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = acc_ref[...] / l_ref[...][..., None]  # [KV, SG, D]


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    qpos: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Causal per-slot attention straight off the page pool.

    Args:
      q: ``[B, S, H, D]`` query rows (post-rope; S = step width).
      k_pool / v_pool: ``[n_pages, bs, KV, D]`` page pool, *after* this
        step's tokens were scattered in (same operand order as the
        gather path).
      block_tables: ``[B, NB]`` int32 logical block -> physical page.
      qpos: ``[B, S]`` int32 absolute query positions (per slot).

    Returns ``[B, S, H, D]`` fp32 (cast at the wrapper).
    """
    b, s, h, d = q.shape
    n_pages, bs_pg, kvh, d2 = k_pool.shape
    assert d == d2 and h % kvh == 0, (q.shape, k_pool.shape)
    nb = block_tables.shape[1]
    # tables are always valid page ids; clip defensively so a bad entry
    # can only read a wrong (causally fenced) page, never out of bounds
    tbl = jnp.clip(block_tables.reshape(-1).astype(jnp.int32), 0, n_pages - 1)
    g = h // kvh
    # Group-major layouts are built here, not in the kernel: Mosaic has
    # no in-kernel reshape that moves S into the sublane axis.
    qg = q.reshape(b, s, kvh, g, d).transpose(0, 2, 1, 3, 4).reshape(b, kvh, s * g, d)
    qpos_rows = jnp.repeat(qpos.astype(jnp.int32), g, axis=1)[..., None]
    spec = specs.paged_attention_spec(
        b=b, s=s, h=h, d=d, n_pages=n_pages, bs_pg=bs_pg, kvh=kvh, nb=nb,
        itemsize=q.dtype.itemsize,
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, bs_pg=bs_pg, nb=nb, scale=1.0 / math.sqrt(d)
        ),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((b, kvh, s * g, d), jnp.float32),
        interpret=interpret,
    )(tbl, qg, k_pool, v_pool, qpos_rows)
    return out.reshape(b, kvh, s, g, d).transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)
