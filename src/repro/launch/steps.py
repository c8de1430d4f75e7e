"""Jitted train/serve step builders shared by train.py, dryrun.py, tests.

``make_classifier_step`` is the ResNet classification step the examples
and ``chip_smoke.py`` jit.

``make_train_step`` builds a gradient-accumulation (microbatched) step:
the global batch is split into ``accum`` microbatches scanned
sequentially with summed grads — at 123B scale the per-device activation
carry of a full 256-batch remat'd scan would exceed HBM; microbatching is
how production frameworks bound it. One optimizer update per step.

``make_serve_step`` is a single-token decode step over the KV/SSM cache.

Both serving steps share :func:`sample_tokens`: sampling parameters ride
in the step state as per-slot *data* arrays (``temps``/``top_ks``/
``top_ps`` plus a ``[B, 2]`` PRNG-lane array ``rng``), so one compiled
executable per step width serves any mix of greedy and sampled slots —
the same "occupancy is data" design as ``count``/``block_tables``. When
the state omits ``rng`` the step falls back to pure greedy argmax
(legacy callers: dryrun, roofline).
"""
from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.policy import DENSE, PolicyLike
from repro.models import model as lm
from repro.models import resnet
from repro.optim import adam


def microbatch_plan(cfg: ModelConfig, shape: ShapeConfig, dp: int) -> int:
    """Number of grad-accumulation microsteps for a train cell.

    Budget ≈ 8k tokens per data shard per microstep (bounds the remat
    carry [micro_b/dp, S, d] · n_layers to ~GBs at d=12k).
    """
    if shape.kind != "train":
        return 1
    budget = max(1, 8192 // shape.seq_len)  # examples per shard
    if cfg.d_model >= 8192:
        budget = 1
    micro_global = min(shape.global_batch, dp * budget)
    accum = max(1, shape.global_batch // micro_global)
    while shape.global_batch % accum:
        accum += 1
    return accum


def make_train_step(
    cfg: ModelConfig,
    policy: PolicyLike,
    opt_cfg: adam.AdamConfig,
    *,
    accum: int = 1,
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss(params, microbatch):
        return lm.loss_fn(cfg, params, microbatch, policy)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def train_step(params, opt_state, batch):
        if accum == 1:
            (loss_v, metrics), grads = grad_fn(params, batch)
        else:
            def reshape(x):
                b = x.shape[0]
                return x.reshape(accum, b // accum, *x.shape[1:])

            micro = jax.tree.map(reshape, batch)

            def body(acc, mb):
                (loss_v, metrics), g = grad_fn(params, mb)
                acc_g, acc_l = acc
                return (
                    jax.tree.map(jnp.add, acc_g, g),
                    acc_l + loss_v / accum,
                ), metrics

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss_v), metrics = jax.lax.scan(body, (zero_g, 0.0), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics)

        new_params, new_opt, om = adam.apply_updates(opt_cfg, params, grads, opt_state)
        metrics = dict(metrics, loss=loss_v, **om)
        return new_params, new_opt, metrics

    return train_step


def classifier_loss(model: str, policy: PolicyLike, params, images, labels):
    """Mean softmax cross-entropy of a ResNet on batch-statistics BatchNorm."""
    logits = resnet.forward(model, params, images, policy)
    logp = jax.nn.log_softmax(logits)
    return -logp[jnp.arange(images.shape[0]), labels].mean()


def make_classifier_step(
    model: str, policy: PolicyLike, opt_cfg: adam.AdamConfig
) -> Callable:
    """(params, opt_state, images, labels) -> (params, opt_state, loss).

    One ResNet classification step as the paper trains it:
    :func:`classifier_loss`, then Adam.
    """

    def classifier_step(params, opt_state, images, labels):
        loss_v, grads = jax.value_and_grad(classifier_loss, argnums=2)(
            model, policy, params, images, labels
        )
        params, opt_state, _ = adam.apply_updates(opt_cfg, params, grads, opt_state)
        return params, opt_state, loss_v

    return classifier_step


def make_eval_step(cfg: ModelConfig) -> Callable:
    def eval_step(params, batch):
        loss_v, metrics = lm.loss_fn(cfg, params, batch, DENSE)
        return metrics["ce"]

    return eval_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """Forward pass over the full prompt (inference-prefill shape)."""

    def prefill(params, batch):
        logits, _ = lm.forward(cfg, params, batch, DENSE)
        return jnp.argmax(logits[:, -1], axis=-1)

    return prefill


# Static bound on per-request top_k: `top_ks` is per-slot *data* (traced),
# but `jax.lax.top_k` needs a static k — so the step computes the top
# TOP_K_CAP values once (O(V·log cap), vs the old full-vocab sort) and
# indexes the k-th per slot. SamplingParams validates top_k <= TOP_K_CAP.
TOP_K_CAP = 128


def sample_tokens(logits, *, rng, temps, top_ks, top_ps, fold):
    """Per-slot temperature / top-k / top-p sampling over ``[B, V]`` logits.

    All controls are per-slot data: ``temps [B]`` (0 = greedy argmax for
    that slot), ``top_ks [B]`` int32 (0 = off), ``top_ps [B]`` (1.0 =
    off), ``rng [B, 2]`` uint32 base PRNG lanes, ``fold [B]`` int32 the
    per-token fold value (the absolute cache position of the token whose
    logits these are). The subkey for each draw is
    ``fold_in(rng[b], fold[b])`` — a pure function of (seed, position),
    so the sampled stream is invariant to chunking, batch composition
    and preemption. Returns ``[B]`` int32 tokens.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    # top-k: mask everything below the k-th largest logit (k = 0 -> off)
    cap = min(v, TOP_K_CAP)
    top_vals, _ = jax.lax.top_k(scaled, cap)  # [B, cap], sorted desc
    kth = jnp.take_along_axis(
        top_vals, (jnp.clip(top_ks, 1, cap) - 1)[:, None], axis=-1
    )
    scaled = jnp.where(
        (top_ks[:, None] > 0) & (scaled < kth), -jnp.inf, scaled
    )
    # top-p (nucleus): keep the smallest sorted prefix with mass >= p.
    # The exclusive cumsum comparison always keeps the top-1 token.
    idx = jnp.argsort(-scaled, axis=-1)
    probs = jax.nn.softmax(jnp.take_along_axis(scaled, idx, axis=-1), axis=-1)
    keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_ps[:, None]
    keep = jnp.take_along_axis(keep_sorted, jnp.argsort(idx, axis=-1), axis=-1)
    scaled = jnp.where(keep, scaled, -jnp.inf)
    keys = jax.vmap(jax.random.fold_in)(rng, fold)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _emit_tokens(logits, state, fold):
    """Greedy-or-sampled next tokens for a serving step's logits."""
    rng = state.get("rng")
    if rng is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return sample_tokens(
        logits, rng=rng, temps=state["temps"], top_ks=state["top_ks"],
        top_ps=state["top_ps"], fold=fold,
    )


def sample_tokens_chunk(logits, *, rng, temps, top_ks, top_ps, fold):
    """Per-position sampling over a ``[B, C, V]`` chunk of logits.

    ``fold [B, C]`` carries each position's absolute cache position.
    Flattens to ``[B*C, V]`` rows and reuses :func:`sample_tokens` with
    each slot's controls repeated across its chunk — every row's
    computation is identical to the width-1 call, so per-position
    emission is bit-exact with single-token decode at the same fold.
    Returns ``[B, C]`` int32 tokens.
    """
    b, c, v = logits.shape

    def rep(a):
        return jnp.repeat(a, c, axis=0)

    toks = sample_tokens(
        logits.reshape(b * c, v), rng=rep(rng), temps=rep(temps),
        top_ks=rep(top_ks), top_ps=rep(top_ps), fold=fold.reshape(b * c),
    )
    return toks.reshape(b, c)


def _emit_chunk_tokens(logits, state, fold):
    """Greedy-or-sampled tokens for every chunk position. [B,C,V] -> [B,C]."""
    rng = state.get("rng")
    if rng is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return sample_tokens_chunk(
        logits, rng=rng, temps=state["temps"], top_ks=state["top_ks"],
        top_ps=state["top_ps"], fold=fold,
    )


def make_serve_step(cfg: ModelConfig) -> Callable:
    """One new token against a seq_len KV cache.

    state = {"tokens": [B,1] int32, "pos": scalar int32, "cache": pytree,
             optional "enc_out": [B, enc_seq, d], optional sampling
             arrays "rng" [B,2] u32 / "temps" [B] / "top_ks" [B] /
             "top_ps" [B] (absent -> greedy)}.
    Returns (next_tokens [B,1], new_state).
    """

    def serve_step(params, state):
        enc_out = state.get("enc_out")
        logits, new_cache = lm.decode_step(
            cfg, params, state["tokens"], state["cache"], state["pos"],
            enc_out=enc_out,
        )
        fold = jnp.full((logits.shape[0],), state["pos"], jnp.int32)
        nxt = _emit_tokens(logits, state, fold)[:, None]
        new_state = dict(state, tokens=nxt, pos=state["pos"] + 1, cache=new_cache)
        return new_state

    return serve_step


def make_slot_step(
    cfg: ModelConfig, *, paged_kernel: bool = False, spec: bool = False
) -> Callable:
    """Mixed prefill/decode step over per-slot state (continuous batching).

    ``paged_kernel=True`` (paged cache only) routes decode attention
    through the Pallas paged-attention kernel — pages read in place via
    the block table instead of the per-layer pool gather.

    state = {"tokens": [B,C] int32, "count": [B] int32 (real tokens per
    slot; 0 = idle), "pos": [B] int32 (per-slot cache offsets),
    "cache": pytree, optional "enc_out": [B, enc_seq, d], optional
    "block_tables": [B, NB] int32 (paged cache: logical block ->
    physical page per slot), optional per-slot sampling arrays
    "rng" [B,2] u32 / "temps" [B] / "top_ks" [B] / "top_ps" [B]
    (absent -> greedy argmax everywhere)}.

    One compiled step serves any slot occupancy: which slots decode,
    which prefill a chunk and which sit idle is *data* (count/pos), not
    shape — with the paged cache the page assignment is data too (block
    tables ride in the state dict), and so are the sampling controls:
    each slot's temperature/top-k/top-p and PRNG lane are arrays, so one
    executable per chunk width serves any mix of greedy and sampled
    slots. The per-token subkey folds the slot's lane by the absolute
    position of its last real token (``pos + count - 1``), keeping the
    sampled stream independent of chunking and preemption. Returns
    ``(next_tokens [B] int32, new_state)`` with the cache written and
    ``pos`` advanced by ``count``; rows with count==0 return garbage
    tokens the scheduler ignores.

    ``spec=True`` builds the speculative verify step instead. The state
    gains ``"is_spec" [B]`` bool; a speculative slot's ``tokens`` row is
    ``[t0, d1, .., d_{n-1}]`` — the last committed token followed by
    ``n-1`` draft proposals — with ``count = n``. The step emits the
    target's token at *every* chunk position with that position's fold
    (``fold[b, j] = pos[b] + j``), accepts the longest prefix where
    draft ``d_{j+1}`` equals the target's token at position ``j``
    (exact-match acceptance), and commits only the accepted prefix:
    ``keep = accepted + 1`` tokens are consumed, ``pos`` advances by
    ``keep``, and the SSM state is selected at the accepted position
    inside the step (:func:`repro.models.model.commit_spec_cache`), so
    the cache pytree out matches the non-speculative layout exactly.
    Rejected KV writes land beyond the committed ``pos``, where the
    per-slot causal mask fences them until they are overwritten.
    Non-speculative rows (``is_spec`` False — prefill chunks, plain
    decode, idle) take ``keep = count``, making this a strict superset
    of the plain step: one executable per width serves any mix. Returns
    ``((tokens [B, C] int32, keep [B] int32), new_state)`` — the caller
    emits ``tokens[b, :keep[b]]`` for a speculative slot and
    ``tokens[b, count[b]-1]`` otherwise.
    """

    def slot_step(params, state):
        if not spec:
            logits, new_cache = lm.decode_slots(
                cfg, params, state["tokens"], state["cache"],
                state["pos"], state["count"], enc_out=state.get("enc_out"),
                block_tables=state.get("block_tables"),
                paged_kernel=paged_kernel,
            )
            nxt = _emit_tokens(logits, state, state["pos"] + state["count"] - 1)
            new_state = dict(
                state, cache=new_cache, pos=state["pos"] + state["count"]
            )
            return nxt, new_state

        tokens, count = state["tokens"], state["count"]
        b, c = tokens.shape
        logits, new_cache = lm.decode_slots(
            cfg, params, tokens, state["cache"],
            state["pos"], count, enc_out=state.get("enc_out"),
            block_tables=state.get("block_tables"),
            paged_kernel=paged_kernel,
            all_logits=True, spec_states=True,
        )
        fold = state["pos"][:, None] + jnp.arange(c)[None, :]  # [B, C]
        tok = _emit_chunk_tokens(logits, state, fold)  # [B, C]
        if c > 1:
            # draft token d_{j+1} rides in the *input* row: accept while
            # the target's token at position j reproduces it.
            matches = (tok[:, :-1] == tokens[:, 1:]) & (
                jnp.arange(c - 1)[None, :] < (count - 1)[:, None]
            )
            acc = jnp.cumprod(matches.astype(jnp.int32), axis=1).sum(axis=1)
            keep = jnp.where(state["is_spec"] & (count > 1), acc + 1, count)
        else:
            keep = count
        new_cache = lm.commit_spec_cache(new_cache, keep)
        new_state = dict(state, cache=new_cache, pos=state["pos"] + keep)
        return (tok, keep), new_state

    return slot_step


def abstract_state(cfg: ModelConfig, rng=None):
    """eval_shape of (params, opt_state) — no allocation."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    a_params = jax.eval_shape(lambda r: lm.init_params(cfg, r), rng)
    a_opt = jax.eval_shape(adam.init, a_params)
    return a_params, a_opt


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.eval_shape(
        lambda: lm.init_cache(cfg, batch, max_seq, dtype=jnp.dtype(cfg.dtype))
    )
