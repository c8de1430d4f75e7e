"""Plain float32 reference of a qwen2 decoder's training step.

For checking the model code (``tests/test_lm_reference.py``): plain
``jax.numpy``, one device, no scan, remat, kernels or cache, every
contraction at float32 'highest'. It shares no code with
``models/``: the weights come in as a tree per layer
(:func:`layer_params` unstacks the program's), and everything else is
written out here.

The decoder is Qwen2's: per layer RMSNorm, attention with bias on q, k
and v, rotary positions (rotate-half, base ``rope_theta``) on q and k,
grouped-query attention by explicit repeat of the key/value heads, a
causal softmax, the output projection and a residual; RMSNorm, the SwiGLU
MLP ``down(silu(gate) * up)`` and a residual; a final RMSNorm and the
unembedding tied to the embedding; next-token cross-entropy, the mean
over every token.

ssProp enters as a top-block mask on each projection's output gradient:
per output channel the mean ``|dY|`` over the tokens, averaged over
blocks of channels, the top blocks kept and the others zeroed, then the
plain product's VJP of what is left. Over ``n > 1`` shards of the output
channels each shard keeps its own top blocks, at a block halved until it
tiles the shard (:func:`block_spec`); with ``n == 1`` the top-k is
global, as in the paper.

Adam as ``optim/adam.py`` states it: global-norm clip, bias-corrected
moments, constant learning rate.

Departures from the published model: the vocabulary's padded rows (the
embedding table is padded for an even split over a mesh) are masked to
-inf in the logits, so they take no probability and no gradient; and
selection is per shard where a tensor-parallel mesh splits the output
channels, where the paper's top-k is global.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PROJECTIONS = {
    "q": "attn", "k": "attn", "v": "attn", "o": "attn",
    "gate": "mlp", "up": "mlp", "down": "mlp",
}


def block_spec(c: int, drop_rate: float, block: int, shards: int = 1) -> tuple[int, int, int]:
    """``(shards, block, kept blocks per shard)`` of a projection with
    ``c`` output channels: over ``n`` shards each keeps ``max(1, min(its
    blocks, K // n))`` of ``K = round((1 - D) * (c // block))``; held
    whole, ``max(1, round((1 - D) * ceil(c / block)))`` blocks are kept,
    a ragged tail block padded with zeros."""
    if shards > 1:
        c_loc = c // shards
        while block > 1 and (c_loc < block or c_loc % block):
            block //= 2
        total = max(1, int(round((1.0 - drop_rate) * (c // block))))
        return shards, block, max(1, min(c_loc // block, total // shards))
    nb = -(-c // block)
    return 1, block, max(1, int(round((1.0 - drop_rate) * nb)))


def keep_mask(dy, spec) -> jax.Array:
    """``[C]`` 0/1 of the output channels whose blocks are kept."""
    n, bs, keep = spec
    c = dy.shape[-1]
    imp = jnp.mean(jnp.abs(dy.reshape(-1, c)), axis=0)
    nb = -(-c // bs)
    blocks = jnp.pad(imp, (0, nb * bs - c)).reshape(n, nb // n, bs).mean(-1)
    _, top = jax.lax.top_k(blocks, keep)
    kept = jnp.zeros_like(blocks).at[jnp.arange(n)[:, None], top].set(1.0)
    return jnp.repeat(kept.reshape(-1), bs)[:c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def projection(x, w, b, spec):
    return x @ w + b


def _projection_fwd(x, w, b, spec):
    return projection(x, w, b, spec), (x, w)


def _projection_bwd(spec, res, dy):
    x, w = res
    dy = dy * keep_mask(dy, spec)
    dy2 = dy.reshape(-1, dy.shape[-1])
    return dy @ w.T, x.reshape(-1, x.shape[-1]).T @ dy2, dy2.sum(0)


projection.defvjp(_projection_fwd, _projection_bwd)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary positions on ``[B, S, H, D]``, rotate-half convention."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def decoder_layer(cfg, specs, p, x):
    """One layer on ``x [B, S, d]``; ``specs[name]`` is each projection's
    :func:`block_spec`."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def proj(name, inp):
        q = p[PROJECTIONS[name]][name]
        bias = q.get("b", jnp.zeros((q["w"].shape[1],), jnp.float32))
        return projection(inp, q["w"], bias, specs[name])

    y = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
    q = rope(proj("q", y).reshape(b, s, h, hd), cfg.rope_theta)
    k = rope(proj("k", y).reshape(b, s, kv, hd), cfg.rope_theta)
    v = proj("v", y).reshape(b, s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(b, s, h * hd)
    x = x + proj("o", att)
    y = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
    return x + proj("down", jax.nn.silu(proj("gate", y)) * proj("up", y))


def loss(cfg, specs, params, tokens, targets):
    """Mean next-token cross-entropy of ``params`` (:func:`layer_params`'
    layout) over ``tokens [B, S]``."""
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0)
    for p in params["layers"]:
        x = decoder_layer(cfg, specs, p, x)
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = x @ table.T
    valid = jnp.arange(table.shape[0]) < cfg.vocab
    logp = jax.nn.log_softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def layer_params(params) -> dict:
    """The program's weights (one stacked slot, layers leading), float32,
    as ``{"embed", "final_norm", "layers": [per layer]}``."""
    (slot,) = params["stack"]["slots"]
    f32 = functools.partial(jax.tree.map, lambda a: jnp.asarray(a, jnp.float32))
    n = jax.tree.leaves(slot)[0].shape[0]
    return {
        "embed": f32(params["embed"]),
        "final_norm": f32(params["final_norm"]),
        "layers": [f32(jax.tree.map(lambda a, i=i: a[i], slot)) for i in range(n)],
    }


def train_step(cfg, specs, opt, params, tokens, targets):
    """One Adam step from zero moments. Returns ``(loss, clipped
    gradient, first moment, new weights)``, each tree in
    :func:`layer_params`' layout."""
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(functools.partial(loss, cfg, specs))(
            params, tokens, targets
        )
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        if opt.clip_norm > 0:
            scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gn, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda g: (1 - opt.b1) * g, grads)
        v = jax.tree.map(lambda g: (1 - opt.b2) * jnp.square(g), grads)
        new = jax.tree.map(
            lambda p, m_, v_: p - opt.lr * (m_ / (1 - opt.b1)) / (jnp.sqrt(v_ / (1 - opt.b2)) + opt.eps),
            params, m, v,
        )
    return value, grads, m, new
