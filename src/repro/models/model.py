"""Top-level LM model: embeddings + stack + loss + decode.

Public functional API (used by the train/serve step builders, the smoke
tests and the dry-run):

  * ``init_params(cfg, rng)``
  * ``forward(cfg, params, batch, policy)``          -> logits
  * ``loss_fn(cfg, params, batch, policy)``          -> (loss, metrics)
  * ``init_cache(cfg, batch, max_seq)``              -> cache pytree
  * ``decode_step(cfg, params, tokens, cache, pos)`` -> (logits, cache)

Batch dict keys by family:
  * LM/MoE/hybrid/ssm: ``tokens [B,S]``, ``targets [B,S]``
  * vlm:   + ``patches [B, n_patches, d_model]`` (SigLIP stub output)
  * encdec:+ ``frames  [B, enc_seq, d_model]``   (audio frontend stub)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.policy import DENSE, PolicyLike
from repro.models import layers, transformer


def init_params(cfg: ModelConfig, rng: jax.Array) -> dict[str, Any]:
    dt = jnp.dtype(cfg.dtype)
    k_emb, k_stack, k_enc, k_out = jax.random.split(rng, 4)
    params: dict[str, Any] = {
        "embed": layers.embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dt),
        "final_norm": layers.rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.family == "encdec":
        params["encoder"] = transformer.encoder_init(k_enc, cfg)
        params["enc_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        params["decoder"] = transformer.cross_decoder_init(k_stack, cfg)
    else:
        params["stack"] = transformer.stack_init(k_stack, cfg)
    return params


def site_names(cfg: ModelConfig):
    """Enumerate every sparsifiable call site of this model.

    Returns ``(sites, depth)`` — the inputs
    :meth:`repro.core.policy.PolicyProgram.resolve` needs: stable site
    names (``layer_{li}/attn/q`` …, see
    :func:`repro.models.transformer.stack_sites`) plus the depth that
    negative layer indices in rule patterns resolve against.
    """
    if cfg.family == "encdec":
        sites = transformer.encoder_sites(cfg) + transformer.cross_decoder_sites(cfg)
    else:
        sites = transformer.stack_sites(cfg)
    return sites, cfg.n_layers


def _embed_inputs(cfg, params, batch):
    """Token embeddings, with the VLM patch prefix fused in."""
    x = layers.embed_apply(params["embed"], batch["tokens"])
    if cfg.family == "vlm":
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    return x


# Name scope of the loss head: the tied unembedding, log-softmax and
# cross-entropy.
LOSS_SCOPE = "lm_loss"


def _final_hidden(cfg: ModelConfig, params, batch, policy: PolicyLike):
    """The final norm's output over the text positions, and the aux loss."""
    x = _embed_inputs(cfg, params, batch)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "encdec":
        enc = transformer.encoder_apply(params["encoder"], batch["frames"].astype(x.dtype), cfg, policy)
        enc = layers.rmsnorm_apply(params["enc_norm"], enc, cfg.norm_eps)
        x, _ = transformer.cross_decoder_apply(params["decoder"], x, enc, cfg, policy)
    else:
        x, _, aux = transformer.stack_apply(params["stack"], x, cfg, policy)
    x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.family == "vlm":
        x = x[:, cfg.n_patches :]
    return x, aux


def forward(
    cfg: ModelConfig,
    params,
    batch: dict[str, jax.Array],
    policy: PolicyLike = DENSE,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits fp32 [B, S, V], aux_loss)."""
    x, aux = _final_hidden(cfg, params, batch, policy)
    return layers.unembed_apply(params["embed"], x, valid=cfg.vocab), aux


def loss_fn(
    cfg: ModelConfig,
    params,
    batch: dict[str, jax.Array],
    policy: PolicyLike = DENSE,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Next-token cross-entropy (+0.01·MoE aux), the loss head under the
    ``lm_loss`` scope."""
    x, aux = _final_hidden(cfg, params, batch, policy)
    targets = batch["targets"]
    with jax.named_scope(LOSS_SCOPE):
        logits = layers.unembed_apply(params["embed"], x, valid=cfg.vocab)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask", jnp.ones_like(targets, jnp.float32))
        loss = (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"ce": loss, "aux": aux}


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    if cfg.family == "encdec":
        def one(k):
            del k
            return {
                "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dt),
            }
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one(None)
        )
    return transformer.stack_cache_init(cfg, batch, max_seq, dt)


def init_paged_cache(
    cfg: ModelConfig, batch: int, n_blocks: int, block_size: int, dtype=None
):
    """Paged decode cache: K/V in a global page pool, SSM state per slot.

    Attention leaves are ``[np, n_blocks, block_size, KV, hd]`` — page
    id *p* addresses the same pool index at every layer, so one block
    table serves the whole stack. SSM conv/state leaves stay batch-major
    ``[np, batch, ...]`` (they are O(1) per slot — nothing to page).
    """
    dt = dtype or jnp.dtype(cfg.dtype)
    if cfg.family == "encdec":
        def one(k):
            del k
            return {
                "k": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim), dt),
            }
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one(None)
        )
    return transformer.stack_cache_init(
        cfg, batch, block_size, dt, n_pages=n_blocks
    )


def decode_slots(
    cfg: ModelConfig,
    params,
    tokens: jax.Array,  # [B, C] — up to C tokens per slot this step
    cache,
    slot_pos: jax.Array,  # [B] int32: per-slot cache write position
    token_count: jax.Array,  # [B] int32: real tokens per slot (0 = idle slot)
    *,
    enc_out: jax.Array | None = None,
    block_tables: jax.Array | None = None,  # [B, NB] int32 (paged cache)
    paged_kernel: bool = False,
    policy: PolicyLike = DENSE,
    all_logits: bool = False,
    spec_states: bool = False,
):
    """Mixed prefill/decode step over independently positioned slots.

    The per-slot cache API for continuous batching: every batch row is a
    *slot* with its own write position — decode slots feed 1 token,
    prefilling slots feed a chunk of up to C prompt tokens, idle slots
    feed 0 — all in one call. KV writes are vectorized scatters at
    ``slot_pos[b] + c`` (invalid tokens dropped); SSM states freeze on
    invalid tokens; attention is causally masked per slot, which also
    fences any stale cache a previous occupant of the slot left behind.

    With ``block_tables`` the cache is the *paged* layout
    (:func:`init_paged_cache`): slot *b*'s token at logical position
    ``p`` lives in page ``block_tables[b, p // block_size]`` at offset
    ``p % block_size``; KV scatters become page-indexed and attention
    gathers K/V through the table. Block tables are data, not shape —
    the same compiled step serves any page assignment. ``paged_kernel``
    replaces that per-layer gather with the Pallas paged-attention
    kernel, which reads the pages in place (same mask semantics).

    Returns ``(logits [B, V] at each slot's last real token, new_cache)``.
    Rows with ``token_count == 0`` carry garbage logits the caller must
    ignore.

    ``all_logits=True`` returns the full chunk's logits ``[B, C, V]``
    instead of the last real token's row — the speculative verifier
    needs every position to compare draft tokens against.
    ``spec_states=True`` additionally makes SSM cache leaves come back
    with a per-position axis (``[np, B, C, ...]``) so
    :func:`commit_spec_cache` can select the state as of an accepted
    prefix; KV leaves are position-addressed and unchanged.
    """
    b, c = tokens.shape
    positions = slot_pos[:, None] + jnp.arange(c)[None, :]  # [B, C]
    valid = jnp.arange(c)[None, :] < token_count[:, None]  # [B, C]
    x = layers.embed_apply(params["embed"], tokens)
    if cfg.family == "encdec":
        x, new_cache = transformer.cross_decoder_apply(
            params["decoder"], x, enc_out, cfg, policy,
            positions=positions, caches=cache, cache_pos=slot_pos,
            token_valid=valid, block_tables=block_tables,
            paged_kernel=paged_kernel,
        )
    else:
        x, new_cache, _ = transformer.stack_apply(
            params["stack"], x, cfg, policy,
            positions=positions, caches=cache, cache_pos=slot_pos,
            token_valid=valid, block_tables=block_tables,
            paged_kernel=paged_kernel, spec_states=spec_states,
        )
    x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if all_logits:
        logits = layers.unembed_apply(params["embed"], x, valid=cfg.vocab)
        return logits, new_cache
    last = jnp.clip(token_count - 1, 0, c - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B, 1, d]
    logits = layers.unembed_apply(params["embed"], x_last, valid=cfg.vocab)[:, 0]
    return logits, new_cache


def commit_spec_cache(cache, keep: jax.Array):
    """Collapse a ``spec_states=True`` cache to the accepted prefix.

    ``keep [B]`` is how many of the chunk's tokens each slot actually
    consumed (``accepted + 1`` for a speculative slot, ``token_count``
    otherwise). SSM leaves carry a per-position axis
    (``conv [np, B, C, ...]`` / ``state [np, B, C, ...]``) — select
    position ``clip(keep - 1, 0)``; a slot with ``keep == 0`` read index
    0, whose state equals the pre-step state because invalid positions
    are frozen in the decode scan. KV leaves are position-addressed
    (rejected writes sit beyond the committed ``pos`` and are fenced by
    the per-slot causal mask, then overwritten) and pass through, so the
    result matches the non-speculative cache pytree exactly.
    """
    idx = jnp.clip(keep - 1, 0)

    def one(path, a):
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        if keys and keys[-1] in ("conv", "state"):
            ix = idx.reshape((1, -1, 1) + (1,) * (a.ndim - 3))
            return jnp.take_along_axis(a, ix, axis=2)[:, :, 0]
        return a

    return jax.tree_util.tree_map_with_path(one, cache)


def reset_slots(cache, free_mask: jax.Array):
    """Zero the cache rows of the slots in ``free_mask [B]`` (bool).

    Every cache leaf is batch-major on axis 1 (``[np, B, ...]`` for the
    period stacks, ``[L, B, ...]`` for the encdec cache), so one
    ``where`` per leaf clears a slot for its next occupant. Mandatory
    for SSM/conv states (they carry no position to mask by); hygienic
    for KV rows.
    """

    def one(a):
        m = free_mask.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, jnp.zeros((), a.dtype), a)

    return jax.tree.map(one, cache)


def reset_paged(cache, slot_mask: jax.Array, page_mask: jax.Array):
    """Zero freed state in a paged cache (:func:`init_paged_cache`).

    K/V leaves (``[np, n_blocks, bs, KV, hd]``) are zeroed by
    ``page_mask [n_blocks]`` on the page axis; everything else (SSM
    conv/state, ``[np, B, ...]``) by ``slot_mask [B]`` on the slot axis.
    One fused device call — the paged analogue of :func:`reset_slots`.
    """

    def one(path, a):
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        mask = page_mask if (keys and keys[-1] in ("k", "v")) else slot_mask
        m = mask.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.where(m, jnp.zeros((), a.dtype), a)

    return jax.tree_util.tree_map_with_path(one, cache)


def swap_out_slot(cache, slot: int, pages):
    """Gather one slot's swappable decode state from a paged cache.

    Returns a pytree mirroring ``cache`` where each K/V leaf holds only
    the slot's ``pages`` (``[np, n_pages, bs, KV, hd]``) and every
    slot-major leaf (SSM conv/state) holds only the slot's row
    (``[np, ...]``). The bundle plus the slot's position is everything a
    swap preemption needs to restore the request's device state exactly
    — the host-swap counterpart of the recompute path in
    ``repro.serve.request``.
    """

    def one(path, a):
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        if keys and keys[-1] in ("k", "v"):
            return a[:, pages]
        return a[:, slot]

    return jax.tree_util.tree_map_with_path(one, cache)


def swap_in_slot(cache, data, slot: int, pages):
    """Scatter a :func:`swap_out_slot` bundle back into a paged cache.

    ``pages`` are the freshly allocated physical pages (same count as at
    swap-out; the ids may differ — block tables are remapped by the
    cache manager, the page *contents* are position-addressed within
    each page so they relocate freely).
    """

    def one(path, a, d):
        keys = [str(k.key) for k in path if hasattr(k, "key")]
        if keys and keys[-1] in ("k", "v"):
            return a.at[:, pages].set(jnp.asarray(d, a.dtype))
        return a.at[:, slot].set(jnp.asarray(d, a.dtype))

    return jax.tree_util.tree_map_with_path(one, cache, data)


def decode_step(
    cfg: ModelConfig,
    params,
    tokens: jax.Array,  # [B, S]
    cache,
    pos: jax.Array,  # scalar int32: current write position
    *,
    enc_out: jax.Array | None = None,
    policy: PolicyLike = DENSE,
):
    """One lock-step decode step (all rows at the same ``pos``).

    The uniform-position special case of :func:`decode_slots`: the
    scalar ``pos`` keeps the cheaper ``dynamic_update_slice`` cache
    write and the batch-shared attention mask. Returns
    (logits [B,V] at the last position, cache).
    """
    b, s = tokens.shape
    x = layers.embed_apply(params["embed"], tokens)
    positions = (pos + jnp.arange(s))[None, :]
    if cfg.family == "encdec":
        x, new_cache = transformer.cross_decoder_apply(
            params["decoder"], x, enc_out, cfg, policy,
            positions=positions, caches=cache, cache_pos=pos,
        )
    else:
        x, new_cache, _ = transformer.stack_apply(
            params["stack"], x, cfg, policy,
            positions=positions, caches=cache, cache_pos=pos,
        )
    x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = layers.unembed_apply(params["embed"], x[:, -1:], valid=cfg.vocab)[:, 0]
    return logits, new_cache


def encode(cfg: ModelConfig, params, frames: jax.Array, policy: PolicyLike = DENSE):
    """Whisper encoder pass (used once before decode)."""
    enc = transformer.encoder_apply(params["encoder"], frames, cfg, policy)
    return layers.rmsnorm_apply(params["enc_norm"], enc, cfg.norm_eps)
