"""Channel importance and top-k gradient selection (paper Fig. 1(a)).

Given an output gradient ``dY``, the paper computes a per-output-channel
importance — the spatial/batch mean of ``|dY|`` — sorts it, and keeps the
top-K channels' gradients for the backward matmuls.

Two granularities are provided (DESIGN.md §3):

* ``"channel"``: per-channel top-k, exactly the paper.
* ``"block"``: top-k over contiguous blocks of ``block_size`` channels —
  the TPU-native form that keeps shrunk matmuls 128-lane/MXU aligned and
  lets the Pallas kernel fuse the gather into HBM→VMEM block addressing.

All functions are jit-safe: K is static, indices are data-dependent.
Returned indices are **sorted ascending** — gathers with monotone indices
lower to cheaper HLO and keep dW scatters coalesced.
"""
from __future__ import annotations

from collections.abc import Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.policy import SsPropPolicy


class Selection(NamedTuple):
    """A complete, static-shape description of one selection decision.

    ``idx`` always holds ``k`` channel indices (sorted ascending, clamped
    into ``[0, C)``). With block granularity and a ragged channel tail
    (``C % block_size != 0``) some slots are phantoms — clamped
    duplicates of ``C-1`` — and ``valid`` marks the real ones; gathers
    must zero the phantom slots and scatters must accumulate with
    ``.add`` so the duplicates cannot overwrite the last real channel.
    ``valid is None`` means every slot is real.

    ``block_idx`` carries the kept *block* indices (global, sorted
    ascending — the form the Pallas gathered kernels consume) when the
    selection was block-granular. Sharded selections populate it too,
    whenever each shard's channel count is a multiple of the policy
    block size (the shard-local block size then equals the global one,
    so per-shard blocks tile exactly into global blocks).
    ``shard_idx``/``k_loc``/``n_shards`` carry the per-shard form for
    TP-local or per-group balanced selection.
    """

    idx: jax.Array
    k: int
    valid: jax.Array | None = None
    block_idx: jax.Array | None = None
    shard_idx: jax.Array | None = None
    k_loc: int = 0
    n_shards: int = 1


def channel_importance(dy: jax.Array, channel_axis: int = -1) -> jax.Array:
    """Mean of ``|dy|`` over every axis except ``channel_axis``.

    Returns a 1-D vector of length ``dy.shape[channel_axis]`` where larger
    values mean the channel "contributes more significantly to the
    gradients of inputs and weights/biases" (paper, Method).
    """
    axis = channel_axis % dy.ndim
    reduce_axes = tuple(a for a in range(dy.ndim) if a != axis)
    # fp32 accumulation: bf16 |dy| means underflow easily at large B*S.
    return jnp.mean(jnp.abs(dy).astype(jnp.float32), axis=reduce_axes)


def block_importance(imp: jax.Array, block_size: int) -> jax.Array:
    """Aggregate per-channel importance into per-block importance.

    Channels are padded with zeros up to a multiple of ``block_size``;
    block importance is the mean over the block (zeros in a ragged tail
    only dilute that tail block, matching "smallest gradients dropped
    first" semantics).
    """
    c = imp.shape[0]
    nblocks = -(-c // block_size)
    pad = nblocks * block_size - c
    if pad:
        imp = jnp.pad(imp, (0, pad))
    return imp.reshape(nblocks, block_size).mean(axis=1)


def select_topk_channels(
    imp: jax.Array,
    k: int,
    *,
    selection: str = "topk",
    key: jax.Array | None = None,
) -> jax.Array:
    """Indices of the K most important channels, sorted ascending.

    ``selection="random"`` reproduces the paper's Fig. 2(b) ablation:
    K channels chosen uniformly at random (requires ``key``).
    """
    c = imp.shape[0]
    if not 0 < k <= c:
        raise ValueError(f"k={k} out of range for {c} channels")
    if selection == "topk":
        _, idx = jax.lax.top_k(imp, k)
    elif selection == "random":
        if key is None:
            raise ValueError("selection='random' requires a PRNG key")
        idx = jax.random.permutation(key, c)[:k]
    else:
        raise ValueError(f"bad selection {selection!r}")
    return jnp.sort(idx)


def select_topk_blocks(
    imp: jax.Array,
    block_size: int,
    k_blocks: int,
    *,
    selection: str = "topk",
    key: jax.Array | None = None,
) -> jax.Array:
    """Indices of the K most important channel *blocks*, sorted ascending."""
    bimp = block_importance(imp, block_size)
    return select_topk_channels(bimp, k_blocks, selection=selection, key=key)


def block_indices_to_channels(block_idx: jax.Array, block_size: int) -> jax.Array:
    """Expand block indices to the flat channel indices they cover."""
    offs = jnp.arange(block_size)
    return (block_idx[:, None] * block_size + offs[None, :]).reshape(-1)


def select(
    dy: jax.Array,
    policy: SsPropPolicy,
    *,
    channel_axis: int = -1,
    n_shards: int = 1,
    key: jax.Array | None = None,
) -> Selection:
    """Policy-driven selection in its full structured form.

    ``n_shards > 1`` partitions the channel axis into that many contiguous
    equal groups and selects a balanced top-k within each — the form used
    both for TP-local selection (comm-free gathers) and for grouped convs
    (a gathered grouped conv stays well-formed only when every group
    keeps the same number of channels).
    """
    c = dy.shape[channel_axis % dy.ndim]
    if n_shards > 1:
        dy2 = jnp.moveaxis(dy, channel_axis % dy.ndim, -1).reshape(-1, c)
        shard_idx, k_loc = select_indices_per_shard(dy2, policy, n_shards, key=key)
        offs = jnp.arange(n_shards)[:, None] * (c // n_shards)
        flat = jnp.sort((shard_idx + offs).reshape(-1))
        block_idx = None
        c_loc = c // n_shards
        if (
            policy.granularity == "block"
            and c_loc % policy.block_size == 0
            and k_loc % policy.block_size == 0
        ):
            # Shard-local blocks tile exactly into global blocks (the
            # per-shard block size was not shrunk), so the flat sorted
            # channel indices regroup into whole kept blocks — the form
            # the Pallas gathered kernels consume. This is what routes
            # grouped convs / TP-local selection onto the fused kernels.
            block_idx = (
                flat.reshape(-1, policy.block_size)[:, 0] // policy.block_size
            )
        return Selection(
            idx=flat,
            k=n_shards * k_loc,
            block_idx=block_idx,
            shard_idx=shard_idx,
            k_loc=k_loc,
            n_shards=n_shards,
        )
    imp = channel_importance(dy, channel_axis)
    if policy.granularity == "channel":
        k = policy.keep_count(c)
        idx = select_topk_channels(imp, k, selection=policy.selection, key=key)
        return Selection(idx=idx, k=k)
    k_blocks = policy.keep_count(c)
    bidx = select_topk_blocks(
        imp, policy.block_size, k_blocks, selection=policy.selection, key=key
    )
    raw = block_indices_to_channels(bidx, policy.block_size)
    # Ragged tail (C % block_size != 0): the tail block covers phantom
    # channels past C-1. Clamp them into range for gathers, and mark them
    # invalid so the engine zeroes their gathered values and scatters
    # with .add — otherwise the clamped duplicates double-count /
    # arbitrarily overwrite channel C-1.
    valid = None
    if c % policy.block_size != 0:
        valid = raw < c
    idx = jnp.minimum(raw, c - 1)
    return Selection(idx=idx, k=k_blocks * policy.block_size, valid=valid, block_idx=bidx)


def select_indices(
    dy: jax.Array,
    policy: SsPropPolicy,
    *,
    channel_axis: int = -1,
    key: jax.Array | None = None,
) -> tuple[jax.Array, int]:
    """Back-compat view of :func:`select`: (sorted channel indices, K).

    For block granularity the indices are the expanded channel indices of
    the kept blocks, tail phantoms clamped to ``C-1``. Safe for building
    keep-masks (a phantom only exists when the tail block was kept, so
    its clamp target is itself a kept channel); gather/scatter callers
    must use :func:`select` and honour ``Selection.valid``.
    """
    sel = select(dy, policy, channel_axis=channel_axis, key=key)
    return sel.idx, sel.k


def keep_mask(
    dy_shape: Sequence[int],
    idx: jax.Array,
    *,
    channel_axis: int = -1,
    dtype=jnp.bool_,
) -> jax.Array:
    """Boolean mask over the channel axis: True on kept channels.

    Used by ``mask_mode`` (reference semantics) and by tests.
    """
    c = dy_shape[channel_axis % len(dy_shape)]
    flat = jnp.zeros((c,), dtype=jnp.bool_).at[idx].set(True)
    shape = [1] * len(dy_shape)
    shape[channel_axis % len(dy_shape)] = c
    return flat.reshape(shape).astype(dtype)


def shard_select_width(
    c: int, policy: SsPropPolicy, n_shards: int
) -> tuple[int, int]:
    """Static ``(k_loc, bs_loc)`` of sharded selection over ``C`` channels.

    ``k_loc`` is the per-shard gathered width (channels each shard keeps)
    and ``bs_loc`` the shard-local block size (halved until it tiles the
    shard; 1 for channel granularity). This is the sizing half of
    :func:`select_indices_per_shard`, split out so the FLOPs tables
    (``core/flops.py``) model the *same* contraction widths the engine
    traces — the honest-savings audit pins them equal, so keep the two
    in one place.
    """
    c_loc = c // n_shards
    if policy.granularity == "block":
        bs = policy.block_size
        while bs > 1 and (c_loc < bs or c_loc % bs):
            bs //= 2
        nblocks_loc = c_loc // bs
        k_total = max(1, int(round((1.0 - policy.drop_rate) * (c // bs))))
        return max(1, min(nblocks_loc, k_total // n_shards)) * bs, bs
    return max(1, policy.keep_count(c) // n_shards), 1


def selection_shards(c: int, policy: SsPropPolicy, groups: int = 1) -> int:
    """How many contiguous channel groups selection balances over (1 =
    global top-k): the policy's TP degree where it divides ``C``, and a
    grouped conv's ``groups`` where that degree does not cover them (a
    gathered grouped conv stays well-formed only under per-group
    balance)."""
    n = policy.tp_shards if policy.tp_shards > 1 and c % policy.tp_shards == 0 else 1
    if groups > 1 and (n < groups or n % groups != 0):
        n = groups
    return n


def keeps_every_channel(c: int, policy: SsPropPolicy, n_shards: int = 1) -> bool:
    """Whether :func:`select` over ``C`` channels keeps all of them.

    Channel granularity keeps ``keep_count(C)`` of ``C`` channels, block
    granularity ``keep_count(C)`` of ``ceil(C / block_size)`` blocks, and
    sharded selection ``k_loc`` of each shard's ``C / n_shards``
    channels (:func:`shard_select_width`). When nothing is dropped the
    selection is the identity, so the backward engine contracts densely
    instead. Every input is static, so under jit this is a Python bool.
    """
    if n_shards > 1:
        return shard_select_width(c, policy, n_shards)[0] == c // n_shards
    k = policy.keep_count(c)
    if policy.granularity == "channel":
        return k == c
    return k == -(-c // policy.block_size)


def select_indices_per_shard(
    dy2: jax.Array,
    policy: SsPropPolicy,
    tp_shards: int,
    *,
    key: jax.Array | None = None,
) -> tuple[jax.Array, int]:
    """TP-local selection: top-k/shard within each of ``tp_shards``
    contiguous channel groups (the TP shards of the output dim).

    Returns (idx [tp_shards, k_local] of *within-shard* channel indices,
    k_local). Selection is balanced across shards by construction, so the
    shrunk matmuls stay load-balanced, and — because the gather uses
    ``take_along_axis`` on the shard-local axis — GSPMD keeps it
    communication-free (DESIGN.md §3.4; §Perf iteration 1).
    """
    m, c = dy2.shape
    assert c % tp_shards == 0, (c, tp_shards)
    c_loc = c // tp_shards
    imp = channel_importance(dy2, -1).reshape(tp_shards, c_loc)
    if policy.granularity == "block":
        # shard-local block size: small projections (e.g. kv with few
        # heads) may hold fewer than block_size channels per shard.
        k_loc, bs = shard_select_width(c, policy, tp_shards)
        nblocks_loc = c_loc // bs
        k_loc_blocks = k_loc // bs
        bimp = imp.reshape(tp_shards, nblocks_loc, bs).mean(-1)
        _, bidx = jax.lax.top_k(bimp, k_loc_blocks)  # [S, kb]
        bidx = jnp.sort(bidx, axis=-1)
        offs = jnp.arange(bs)
        idx = (bidx[:, :, None] * bs + offs[None, None, :]).reshape(tp_shards, -1)
        return idx, k_loc
    k_loc, _ = shard_select_width(c, policy, tp_shards)
    if policy.selection == "random":
        if key is None:
            raise ValueError("random selection requires key")
        noise = jax.random.uniform(key, imp.shape)
        _, idx = jax.lax.top_k(noise, k_loc)
    else:
        _, idx = jax.lax.top_k(imp, k_loc)
    return jnp.sort(idx, axis=-1), k_loc


def mask_grad(
    dy: jax.Array,
    policy: SsPropPolicy,
    *,
    channel_axis: int = -1,
    key: jax.Array | None = None,
) -> jax.Array:
    """Zero out dropped channels of ``dy`` (mask-mode sparsification)."""
    if not policy.active:
        return dy
    idx, _ = select_indices(dy, policy, channel_axis=channel_axis, key=key)
    m = keep_mask(dy.shape, idx, channel_axis=channel_axis, dtype=dy.dtype)
    return dy * m
