"""Shared model layers (pure-JAX, functional, pytree params).

Every projection matmul routes through :func:`repro.core.sparse_dense`
and every convolution through :func:`repro.core.sparse_conv2d` — via
:func:`dense_apply` / :func:`conv_apply` below — so the ssProp policy
(and the unified backward engine behind it) applies uniformly across
architectures: transformers, ResNets and the DDPM UNet all sparsify
through the same ``repro.core.backward`` pipeline. Attention is
memory-blocked (scan over query chunks with full-K masked scores) so
32k-prefill fits HBM without materializing the full S×S score tensor.

Every call site carries a *site name* (``site=``): with a plain
:class:`~repro.core.policy.SsPropPolicy` the name is ignored (the
legacy global-policy path), while a resolved
:class:`~repro.core.policy.SitePolicies` table gives each named site
its own policy — the per-site control surface of a
:class:`~repro.core.policy.PolicyProgram`. The name also scopes the
call (``jax.named_scope``), so every operation of the site, forward and
backward, carries it in its HLO ``op_name``: ``jvp(block_0/conv1)/...``
in the forward, ``transpose(jvp(block_0/conv1))/...`` in the backward.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.core import sparse_conv2d, sparse_dense
from repro.core.policy import PolicyLike, policy_for


def _scope(site: str):
    """The site's name scope; none for an unnamed call."""
    return jax.named_scope(site) if site else contextlib.nullcontext()


# ----------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------


def dense_init(key, d_in, d_out, *, bias=False, dtype=jnp.bfloat16, scale=None):
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x, policy: PolicyLike, key=None, site: str = ""):
    with _scope(site):
        return sparse_dense(
            x, p["w"], p.get("b"), policy=policy_for(policy, site), key=key
        )


def conv2d_init(key, c_out, c_in, k, *, bias=False, dtype=jnp.float32):
    """Kaiming-normal OIHW conv params: ``{"w"[, "b"]}``."""
    fan_in = c_in * k * k
    w = jax.random.normal(key, (c_out, c_in, k, k), jnp.float32) * math.sqrt(
        2.0 / fan_in
    )
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def conv_apply(
    p,
    x,
    policy: PolicyLike,
    *,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    key=None,
    site: str = "",
):
    """The single conv call site the CNN models share (mirrors
    :func:`dense_apply`): params dict in, ssProp-backward conv out."""
    with _scope(site):
        return sparse_conv2d(
            x,
            p["w"],
            p.get("b"),
            stride=stride,
            padding=padding,
            dilation=dilation,
            groups=groups,
            policy=policy_for(policy, site),
            key=key,
        )


def rmsnorm_init(d, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] or [S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------


def attn_init(key, cfg, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "q": dense_init(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "k": dense_init(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "v": dense_init(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "o": dense_init(
            ks[3], cfg.n_heads * hd, d, dtype=dtype, scale=1.0 / math.sqrt(2 * cfg.n_layers * cfg.n_heads * hd)
        ),
    }


def _gqa_scores(q, k):
    """q [B,S,H,D], k [B,T,KV,D] -> scores [B,H,S,T] with GQA grouping.

    Implemented as repeat-to-full-heads + plain batched dot: the repeat
    fuses into the dot, and — unlike a [KV, H/KV] reshape of the sharded
    head axis — it keeps a TP-sharded q-head axis local when k/v are
    replicated (§Perf iteration 4: kv-heads < TP degree otherwise forces
    GSPMD to reshard the S×T score tensor every layer).
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    k_full = jnp.repeat(k, h // kv, axis=2)  # [B,T,H,D]
    return jnp.einsum(
        "bshd,bthd->bhst", q, k_full, preferred_element_type=jnp.float32
    )


def _gqa_out(probs, v):
    """probs [B,H,S,T], v [B,T,KV,D] -> [B,S,H,D]."""
    b, h, s, t = probs.shape
    kv = v.shape[2]
    v_full = jnp.repeat(v, h // kv, axis=2)  # [B,T,H,D]
    return jnp.einsum("bhst,bthd->bshd", probs, v_full.astype(jnp.float32))


# Name scope of attention's own work (scores, mask, softmax and the
# probabilities' product with V), apart from its projections' sites.
ATTN_CORE_SCOPE = "attn_core"


def masked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_len: jax.Array | None = None,
    seq_shard_hint: bool = False,
    qpos: jax.Array | None = None,
) -> jax.Array:
    """Blocked attention: scan over query chunks, full-K masked scores.

    q [B,S,H,D], k/v [B,T,KV,D]. ``q_offset`` is the absolute position of
    q[0] (decode). ``kv_len`` optionally masks positions >= kv_len
    (padded KV caches). ``qpos [B,S]`` gives *per-slot* absolute query
    positions (continuous batching: each batch row decodes at its own
    offset); it supersedes ``q_offset``/``kv_len`` and the mask gains a
    batch dim. Returns [B,S,H,D] in q.dtype.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, s)
    nchunks = max(1, -(-s // q_chunk))
    pad = nchunks * q_chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if qpos is not None:
            qpos = jnp.pad(qpos, ((0, 0), (0, pad)))
    qs = q.reshape(b, nchunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    per_slot = qpos is not None
    if per_slot:
        qpos_chunks = qpos.reshape(b, nchunks, q_chunk).transpose(1, 0, 2)
    else:
        qpos_chunks = jnp.arange(nchunks)

    kv_positions = jnp.arange(t)

    def body(carry, args):
        with jax.named_scope(ATTN_CORE_SCOPE):
            return carry, _core(*args)

    def _core(qc, qp):
        scores = _gqa_scores(qc, k) * scale  # [B,H,qc,T] fp32
        if seq_shard_hint:
            # §Perf iter 3: keep decode scores sharded on the KV-seq dim
            # (partial-softmax); stops GSPMD gathering the whole cache.
            scores = jax.lax.with_sharding_constraint(
                scores, jax.sharding.PartitionSpec(None, None, None, "model")
            )
        if per_slot:
            # qp [B,qc] absolute per-slot positions -> mask [B,qc,T].
            # Causality alone fences stale cache rows from an evicted
            # request: every live kv row sits at a position <= its qpos.
            mask = jnp.ones((b, q_chunk, t), bool)
            if causal:
                mask &= kv_positions[None, None, :] <= qp[:, :, None]
            scores = jnp.where(mask[:, None], scores, -1e30)
        else:
            qpos_c = q_offset + qp * q_chunk + jnp.arange(q_chunk)
            mask = jnp.ones((q_chunk, t), bool)
            if causal:
                mask &= kv_positions[None, :] <= qpos_c[:, None]
            if kv_len is not None:
                mask &= kv_positions[None, :] < kv_len
            scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return _gqa_out(probs, v)

    _, outs = jax.lax.scan(body, None, (qs, qpos_chunks))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nchunks * q_chunk, h, d)
    return out[:, :s].astype(q.dtype)


def attn_apply(
    p,
    x,
    cfg,
    policy: PolicyLike,
    *,
    causal=True,
    positions=None,
    kv_cache=None,
    cache_pos=None,
    token_valid=None,
    block_tables=None,
    paged_kernel=False,
    x_kv=None,
    use_rope=True,
    site: str = "attn",
):
    """Self- or cross-attention. ``site`` prefixes the per-projection
    policy lookups (``{site}/q`` … ``{site}/o`` — "attn" in the decoder
    stack and encoder, "self"/"cross" in the cross-decoder).

    x [B,S,d]. ``x_kv`` switches to cross-attention (no cache, no rope on
    kv source positions beyond its own). ``kv_cache`` = dict(k, v) of
    shape [B, T, KV, D] for decode; ``cache_pos`` is the write offset —
    a scalar (lock-step: every row writes at the same position) or a
    ``[B]`` array (continuous batching: each slot writes at its own
    position, a vectorized scatter). ``token_valid [B,S]`` masks which
    tokens are real per slot; invalid tokens' k/v are dropped instead of
    written (their query outputs are garbage the caller never reads).

    ``block_tables [B, NB]`` switches the cache to the *paged* layout:
    ``kv_cache`` leaves are a page pool ``[n_pages, bs, KV, D]`` shared
    by all slots, and slot b's token at absolute position p lives in
    page ``block_tables[b, p // bs]`` at offset ``p % bs``. Writes
    become page-indexed scatters (invalid tokens routed to page index
    ``n_pages`` and dropped); attention gathers K/V back through the
    table into the same ``[B, NB*bs, KV, D]`` view the contiguous path
    uses. Unassigned table entries are 0 — a valid page whose contents
    sit at masked (future) positions, so per-slot causality fences them
    exactly like stale rows in the contiguous layout.

    ``paged_kernel=True`` (paged layout only) replaces that per-layer
    gather with the Pallas paged-attention kernel
    (:mod:`repro.kernels.paged_attention`): K/V pages are read *in
    place* from the pool during the kernel's HBM→VMEM copies, so the
    contiguous ``[B, NB*bs, KV, D]`` view is never materialized.
    Returns (out [B,S,d], new_cache or None).
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = dense_apply(p["q"], x, policy, site=f"{site}/q").reshape(b, s, cfg.n_heads, hd)
    src = x if x_kv is None else x_kv
    k = dense_apply(p["k"], src, policy, site=f"{site}/k").reshape(
        b, src.shape[1], cfg.n_kv_heads, hd
    )
    v = dense_apply(p["v"], src, policy, site=f"{site}/v").reshape(
        b, src.shape[1], cfg.n_kv_heads, hd
    )

    per_slot = cache_pos is not None and getattr(cache_pos, "ndim", 0) >= 1
    if positions is None:
        positions = jnp.arange(s)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if x_kv is None:
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    q_offset = 0
    kv_len = None
    qpos = None
    if kv_cache is not None and block_tables is not None:
        # Paged cache: pool leaves [n_pages, bs, KV, D], no batch dim.
        n_pages, bs_pg = kv_cache["k"].shape[:2]
        nb = block_tables.shape[1]
        logical = cache_pos[:, None] + jnp.arange(s)[None, :]  # [B,S]
        blk = jnp.clip(logical // bs_pg, 0, nb - 1)
        off = logical % bs_pg
        page = jnp.take_along_axis(block_tables, blk, axis=1)  # [B,S]
        if token_valid is not None:
            page = jnp.where(token_valid, page, n_pages)  # OOB -> dropped
        ck = kv_cache["k"].at[page, off].set(k, mode="drop")
        cv = kv_cache["v"].at[page, off].set(v, mode="drop")
        new_cache = {"k": ck, "v": cv}
        qpos = positions if positions.ndim == 2 else logical
        if paged_kernel:
            from repro.kernels import ops as kops

            out = kops.paged_attention(q, ck, cv, block_tables, qpos)
            out = out.reshape(b, s, cfg.n_heads * hd)
            return dense_apply(p["o"], out, policy, site=f"{site}/o"), new_cache
        # Gather each slot's pages into the [B, NB*bs, KV, D] view the
        # masked attention consumes (T = NB*bs = max_seq rounded up).
        k = ck[block_tables].reshape(b, nb * bs_pg, *ck.shape[2:])
        v = cv[block_tables].reshape(b, nb * bs_pg, *cv.shape[2:])
    elif kv_cache is not None:
        t = kv_cache["k"].shape[1]
        if per_slot:
            # Vectorized per-slot write: row b's token c lands at
            # cache_pos[b] + c; invalid tokens are routed out of range
            # and dropped by the scatter.
            tgt = cache_pos[:, None] + jnp.arange(s)[None, :]  # [B,S]
            if token_valid is not None:
                tgt = jnp.where(token_valid, tgt, t)
            bidx = jnp.arange(b)[:, None]
            ck = kv_cache["k"].at[bidx, tgt].set(k, mode="drop")
            cv = kv_cache["v"].at[bidx, tgt].set(v, mode="drop")
            qpos = (
                positions
                if positions.ndim == 2
                else cache_pos[:, None] + jnp.arange(s)[None, :]
            )
        else:
            ck = jax.lax.dynamic_update_slice(kv_cache["k"], k, (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(kv_cache["v"], v, (0, cache_pos, 0, 0))
            q_offset = cache_pos
            kv_len = cache_pos + s
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv

    out = masked_attention(
        q, k, v, causal=causal and x_kv is None, q_offset=q_offset, kv_len=kv_len,
        q_chunk=getattr(cfg, "attn_q_chunk", 1024),
        seq_shard_hint=(
            kv_cache is not None and getattr(cfg, "decode_seq_shard", False)
        ),
        qpos=qpos,
    )
    out = out.reshape(b, s, cfg.n_heads * hd)
    return dense_apply(p["o"], out, policy, site=f"{site}/o"), new_cache


# ----------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def mlp_init(key, d_model, d_ff, dtype=jnp.bfloat16, gated: bool = True):
    ks = jax.random.split(key, 3)
    p = {
        "up": dense_init(ks[0], d_model, d_ff, dtype=dtype),
        "down": dense_init(ks[2], d_ff, d_model, dtype=dtype),
    }
    if gated:
        p["gate"] = dense_init(ks[1], d_model, d_ff, dtype=dtype)
    return p


def mlp_apply(p, x, act: str, policy: PolicyLike, site: str = "mlp"):
    if "gate" in p:
        h = _ACTS[act](dense_apply(p["gate"], x, policy, site=f"{site}/gate")) * dense_apply(
            p["up"], x, policy, site=f"{site}/up"
        )
    else:
        h = _ACTS[act](dense_apply(p["up"], x, policy, site=f"{site}/up"))
    return dense_apply(p["down"], h, policy, site=f"{site}/down")


# ----------------------------------------------------------------------
# embeddings
# ----------------------------------------------------------------------


def embed_init(key, vocab, d_model, dtype=jnp.bfloat16):
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02).astype(dtype)}


def embed_apply(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed_apply(p, x, valid: int | None = None):
    """Tied unembedding: x [B,S,d] @ table^T -> logits fp32.

    ``valid``: logical vocab size — logits of padded table rows (vocab
    rounded up for TP sharding) are masked to -inf so softmax/argmax
    never see them.
    """
    logits = jnp.einsum(
        "bsd,vd->bsv", x, p["table"], preferred_element_type=jnp.float32
    )
    v = p["table"].shape[0]
    if valid is not None and valid < v:
        mask = jnp.arange(v) < valid
        logits = jnp.where(mask, logits, -1e30)
    return logits
