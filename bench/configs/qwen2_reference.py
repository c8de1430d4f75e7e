"""Plain reference of qwen2.5 training, in jax.numpy and float32.

It imports nothing of the program. The decoder follows the published
Qwen2 architecture: token embedding; per layer RMSNorm, attention with
bias on q, k and v, rotary positions (rotate-half, base ``rope_theta``)
on q and k, grouped-query attention by explicit repeat of the key/value
heads, a causal softmax, the output projection without bias and a
residual; then RMSNorm and the SwiGLU MLP (``down(silu(gate) * up)``) and
a residual; a final RMSNorm and the unembedding tied to the embedding;
next-token cross-entropy, the mean over every token. Every contraction
computes in float32 at 'highest' precision; weights are stored in the
configuration's ``param_dtype`` and cast up where they are used.

ssProp enters as a top-block mask on each projection's output gradient:
per output channel the mean ``|dY|`` over the tokens, averaged over
channel blocks, the top blocks kept, the others zeroed, and the plain
product's VJP taken of what is left (dX, dW and the bias's gradient).
Where the configuration's mesh splits a projection's output channels
(the column-parallel q, k, v, gate and up), selection is balanced: each
shard keeps its own top blocks, at a block halved until it tiles the
shard (:func:`selection`); elsewhere (o, down) it is global. Blocks whose
importance ties to rounding may be kept on one side and dropped on the
other, so each step takes ``picks``, the blocks the run under check kept:
where a pick keeps as many blocks as the site does, the reference keeps
those and reports its own importance of every block (``selection_gap``).

Optimizer: Adam with the configuration's betas, epsilon and learning
rate, after clipping the gradient to global norm ``clip_norm``; the new
weights are rounded to ``param_dtype`` as the configuration stores them.

Departures from the published model, each stated: the vocabulary is
padded to a multiple of 256 rows (as the program's embedding is, for an
even split over the mesh) and the padded rows' logits are masked to
-inf, so they take no probability and no gradient; selection is per
shard where the mesh splits the output channels, where the paper's is a
global top-k; the loss head is computed in blocks of tokens, and the
backward layer by layer from each layer's stored input (the forward
keeps only those), so that neither the logits nor every layer's
activations stand whole; neither changes a number beyond rounding.

``kind`` puts a control or a fault in the reference's place:
``precision="fp8"`` rounds both operands of every contraction (the
projections forward and backward, attention's two products and the head)
to float8_e4m3fn at a per-tensor scale; ``fault`` is ``"half_batch"``
(the loss over the first half of the batch only), ``"wrong_block"`` (the
least important blocks kept) or ``"dw_x2"`` (every projection's weight
gradient doubled). They are data of one compiled reference.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

PROJECTIONS = {
    "q": "attn", "k": "attn", "v": "attn", "o": "attn",
    "gate": "mlp", "up": "mlp", "down": "mlp",
}
COLUMN = frozenset({"q", "k", "v", "gate", "up"})
HEAD_TOKENS = 1024  # tokens per block of the loss head


# ----------------------------------------------------------------------
# shapes and selection
# ----------------------------------------------------------------------
def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def out_channels(cfg: dict) -> dict[str, int]:
    hd = head_dim(cfg)
    return {
        "q": cfg["num_attention_heads"] * hd,
        "k": cfg["num_key_value_heads"] * hd,
        "v": cfg["num_key_value_heads"] * hd,
        "o": cfg["hidden_size"],
        "gate": cfg["intermediate_size"],
        "up": cfg["intermediate_size"],
        "down": cfg["hidden_size"],
    }


def selection(cfg: dict, mix: dict, shards: int) -> dict[str, tuple[int, int, int]]:
    """Per projection ``(shards, block, kept blocks per shard)``.

    Over ``n`` shards of a split output axis each shard keeps
    ``max(1, min(its blocks, K // n))`` blocks, ``K`` the blocks that
    ``1 - drop_rate`` of the whole axis holds, at a block halved until it
    tiles the shard; an axis held whole keeps ``max(1, round((1 - D) *
    ceil(C / block)))`` blocks, a ragged tail block padded with zeros.
    At drop rate 0 every block is kept.
    """
    d = float(mix["drop_rate"])
    out = {}
    for name, c in out_channels(cfg).items():
        n = shards if name in COLUMN and shards > 1 and c % shards == 0 else 1
        bs = mix["block_size"] if mix["granularity"] == "block" else 1
        if n > 1:
            c_loc = c // n
            while bs > 1 and (c_loc < bs or c_loc % bs):
                bs //= 2
            nb = c_loc // bs
            total = max(1, int(round((1.0 - d) * (c // bs))))
            keep = nb if d <= 0.0 else max(1, min(nb, total // n))
        else:
            nb = -(-c // bs)
            keep = nb if d <= 0.0 else max(1, int(round((1.0 - d) * nb)))
        out[name] = (n, bs, keep)
    return out


def site_keys(cfg: dict, sel: dict) -> dict[str, tuple[str, str, int, int]]:
    """``{reading key: (site, projection, shard, blocks)}``: one key per
    site, or per shard of a site whose selection is balanced over shards
    (``layer_3/mlp/up#2``)."""
    keys = {}
    c_out = out_channels(cfg)
    for i in range(cfg["num_hidden_layers"]):
        for name, role in PROJECTIONS.items():
            n, bs, _ = sel[name]
            site = f"layer_{i}/{role}/{name}"
            nb = -(-c_out[name] // bs) // n
            for j in range(n):
                keys[f"{site}#{j}" if n > 1 else site] = (site, name, j, nb)
    return keys


# ----------------------------------------------------------------------
# weights, as the program's initializer draws them
# ----------------------------------------------------------------------
def _dense(key, d_in, d_out, dtype, scale=None, bias=False):
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_layer(cfg: dict, key: jax.Array) -> dict:
    """One layer's weights from its split of the key: attention's four
    projections and the MLP's three each from its own split; norms 1,
    biases 0."""
    dt = jnp.dtype(cfg["param_dtype"])
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    c = out_channels(cfg)
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    m = jax.random.split(ks[1], 3)
    return {
        "norm1": {"scale": jnp.ones((d,), dt)},
        "attn": {
            "q": _dense(a[0], d, c["q"], dt, bias=True),
            "k": _dense(a[1], d, c["k"], dt, bias=True),
            "v": _dense(a[2], d, c["v"], dt, bias=True),
            "o": _dense(a[3], c["q"], d, dt, scale=1.0 / math.sqrt(2 * L * c["q"])),
        },
        "norm2": {"scale": jnp.ones((d,), dt)},
        "mlp": {
            "up": _dense(m[0], d, ff, dt),
            "gate": _dense(m[1], d, ff, dt),
            "down": _dense(m[2], ff, d, dt),
        },
    }


def init_top(cfg: dict, key: jax.Array) -> dict:
    """The embedding table from ``key``, the final norm 1."""
    dt = jnp.dtype(cfg["param_dtype"])
    table = jax.random.normal(key, (padded_vocab(cfg), cfg["hidden_size"]), jnp.float32) * 0.02
    return {
        "embed": {"table": table.astype(dt)},
        "final_norm": {"scale": jnp.ones((cfg["hidden_size"],), dt)},
    }


def layer_keys(cfg: dict, key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The embedding's key (the first of four splits of ``key``) and each
    layer's (the splits of the second)."""
    k_emb, k_stack, _, _ = jax.random.split(key, 4)
    return k_emb, jax.random.split(k_stack, cfg["num_hidden_layers"])


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Every weight, as the program's initializer draws them."""
    k_emb, ks = layer_keys(cfg, key)
    return {**init_top(cfg, k_emb), "layers": [init_layer(cfg, k) for k in ks]}


def split_layers(tree) -> tuple[dict, list]:
    """``tree`` without its layers, and a list of its layers."""
    return {k: v for k, v in tree.items() if k != "layers"}, list(tree["layers"])


def over_layers(fn, *trees) -> dict:
    """``fn`` of the trees' top-level leaves and of each layer in turn:
    one compile for the top and one for every layer, where a jitted
    function of the whole tree would unroll all of them."""
    tops, layers = zip(*map(split_layers, trees), strict=True)
    return {**fn(*tops), "layers": [fn(*ls) for ls in zip(*layers, strict=True)]}


def leaf_names(tree) -> dict:
    """``{name: leaf}``: ``embed/table``, ``final_norm/scale``,
    ``layer_3/attn/q/w``, ..."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if parts[0] == "layers":
            parts = [f"layer_{parts[1]}", *parts[2:]]
        out["/".join(parts)] = leaf
    return out


def _spec(name: str) -> P:
    """Placement on a ``("data", "model")`` mesh, as a tensor-parallel
    deployment holds it: the table split by rows, column-parallel
    kernels and biases by output channels, row-parallel ones by input."""
    parts = name.split("/")
    if parts[0] == "embed":
        return P("model", None)
    proj = parts[-2] if len(parts) > 2 else ""
    if proj in COLUMN:
        return P(None, "model") if parts[-1] == "w" else P("model")
    if proj in ("o", "down"):
        return P("model", None)
    return P()


def shardings(mesh, tree):
    """Each leaf's ``NamedSharding`` on ``mesh`` (None: one device)."""
    if mesh is None:
        return None
    names = iter(leaf_names(tree))
    return jax.tree.map(lambda _: NamedSharding(mesh, _spec(next(names))), tree)


# ----------------------------------------------------------------------
# controls and faults: data, so that one compile serves every kind
# ----------------------------------------------------------------------
def fp8(a):
    """``a`` rounded to float8_e4m3fn at a per-tensor scale (amax -> 448)."""
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s


def _rnd(a, ctl):
    return jnp.where(ctl[0] > 0, fp8(a), a)


def control(precision: str = "float32", fault: str = "") -> np.ndarray:
    """``[fp8 on, wrong block on, weight-gradient scale]``."""
    if precision not in ("float32", "fp8") or fault not in ("", "half_batch", "wrong_block", "dw_x2"):
        raise ValueError(f"unknown kind {precision!r}, {fault!r}")
    return np.array(
        [precision == "fp8", fault == "wrong_block", 2.0 if fault == "dw_x2" else 1.0],
        np.float32,
    )


def token_weights(batch: int, seq: int, fault: str = "") -> np.ndarray:
    w = np.ones((batch, seq), np.float32)
    if fault == "half_batch":
        w[batch // 2:] = 0.0
    return w


# ----------------------------------------------------------------------
# the ssProp projection
# ----------------------------------------------------------------------
def importance(dy, spec):
    """``[shards, blocks]`` mean ``|dY|`` of each block of output channels."""
    n, bs, _ = spec
    imp = jnp.mean(jnp.abs(dy.reshape(-1, dy.shape[-1])), axis=0)
    c = imp.shape[0]
    if n > 1:
        return imp.reshape(n, c // n // bs, bs).mean(-1)
    nb = -(-c // bs)
    return jnp.pad(imp, (0, nb * bs - c)).reshape(1, nb, bs).mean(-1)


def keep_mask(imp, pick, spec, ctl):
    """``[shards, blocks]`` 0/1 of the kept blocks: each shard's pick
    where it keeps as many blocks as the site does, else its own top."""
    _, _, keep = spec
    score = jnp.where(ctl[1] > 0, -imp, imp)
    _, top = jax.lax.top_k(score, keep)
    own = jax.vmap(lambda t: jnp.zeros(imp.shape[1], imp.dtype).at[t].set(1.0))(top)
    follow = jnp.sum(pick, axis=1, keepdims=True) == keep
    return jnp.where(follow, pick, own)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def projection(x, w, b, pick, ctl, spec):
    return _rnd(x, ctl) @ _rnd(w, ctl) + b


def _proj_fwd(x, w, b, pick, ctl, spec):
    return projection(x, w, b, pick, ctl, spec), (x, w, pick, ctl)


def _proj_bwd(spec, res, dy):
    """The plain VJP of the kept blocks; ``pick``'s cotangent carries the
    blocks' importance out to the caller."""
    x, w, pick, ctl = res
    n, bs, keep = spec
    imp = importance(dy, spec)
    if keep < imp.shape[1]:
        mask = keep_mask(imp, pick, spec, ctl)
        c = dy.shape[-1]
        dy = dy * jnp.repeat(mask.reshape(-1), bs)[:c]
    dyr = _rnd(dy, ctl)
    dx = dyr @ _rnd(w, ctl).T
    x2 = _rnd(x, ctl).reshape(-1, x.shape[-1])
    dw = x2.T @ dyr.reshape(-1, dy.shape[-1]) * ctl[2]
    db = dy.reshape(-1, dy.shape[-1]).sum(0)
    return dx, dw, db, imp, jnp.zeros_like(ctl)


projection.defvjp(_proj_fwd, _proj_bwd)


# ----------------------------------------------------------------------
# the decoder
# ----------------------------------------------------------------------
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


def layer(cfg, sel, p, picks, ctl, x):
    """One decoder layer, float32: ``x [B, S, d]`` to the same."""
    b, s, d = x.shape
    hd, h, kv = head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]

    def proj(part, name, inp):
        q = p[part][name]
        bias = q["b"] if "b" in q else jnp.zeros((q["w"].shape[1],), jnp.float32)
        return projection(inp, q["w"], bias, picks[name], ctl, sel[name])

    y = rmsnorm(x, p["norm1"]["scale"], eps)
    q = rope(proj("attn", "q", y).reshape(b, s, h, hd), cfg["rope_theta"])
    k = rope(proj("attn", "k", y).reshape(b, s, kv, hd), cfg["rope_theta"])
    v = proj("attn", "v", y).reshape(b, s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", _rnd(q, ctl), _rnd(k, ctl)) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhst,bthd->bshd", _rnd(probs, ctl), _rnd(v, ctl))
    x = x + proj("attn", "o", att.reshape(b, s, h * hd))
    y = rmsnorm(x, p["norm2"]["scale"], eps)
    mid = jax.nn.silu(proj("mlp", "gate", y)) * proj("mlp", "up", y)
    return x + proj("mlp", "down", mid)


def head_loss(cfg, table, scale, ctl, x, targets, weights):
    """``sum(weights * nll)`` over a block of tokens ``x [T, d]``: final
    norm, tied unembedding with the padded rows masked, log-softmax."""
    y = rmsnorm(x, scale, cfg["rms_norm_eps"])
    logits = _rnd(y, ctl) @ _rnd(table, ctl).T
    valid = jnp.arange(table.shape[0]) < cfg["vocab_size"]
    logp = jax.nn.log_softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * nll)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def block_norms(dw, spec):
    """``[shards, blocks]`` norm of each block of a kernel's gradient
    columns (output channels): a dropped block's columns are zero."""
    n, bs, _ = spec
    cols = jnp.sum(jnp.square(dw.astype(jnp.float32)), axis=0)
    c = cols.shape[0]
    nb = -(-c // bs)
    return jnp.sqrt(jnp.pad(cols, (0, nb * bs - c)).reshape(n, nb // n, bs).sum(-1))


# ----------------------------------------------------------------------
# the jitted pieces of one training step
# ----------------------------------------------------------------------
class Step:
    """The reference's compiled pieces for one configuration, mix and
    mesh: every kind of run (:func:`control`) reuses them."""

    def __init__(self, cfg: dict, mix: dict, mesh=None, shards: int | None = None):
        self.cfg, self.mix, self.mesh = cfg, mix, mesh
        if shards is None:
            shards = dict(mesh.shape).get("model", 1) if mesh is not None else 1
        self.sel = selection(cfg, mix, shards)
        sel = self.sel
        opt = cfg["optimizer"]
        seq_spec = (
            NamedSharding(mesh, P(None, "model", None)) if mesh is not None else None
        )

        def fwd(p, picks, ctl, x):
            return layer(cfg, sel, _f32(p), picks, ctl, x)

        def bwd(p, picks, ctl, x, dy):
            _, vjp = jax.vjp(lambda a, k, h: layer(cfg, sel, a, k, ctl, h), _f32(p), picks, x)
            g, imp, dx = vjp(dy)
            kept = {n: block_norms(g[PROJECTIONS[n]][n]["w"], sel[n]) for n in PROJECTIONS}
            return dx, g, imp, kept

        def head(table, scale, ctl, x, targets, weights):
            return jax.value_and_grad(head_loss, argnums=(1, 2, 4))(
                cfg, table.astype(jnp.float32), scale.astype(jnp.float32), ctl, x,
                targets, weights,
            )

        def embed(table, tokens):
            return jnp.take(table, tokens, axis=0).astype(jnp.float32)

        def embed_grad(g_table, tokens, dx):
            return g_table.at[tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))

        def sq_sum(g):
            return sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(g))

        def adam(p, m, v, g, clip, t):
            """Adam at step ``t`` (from 1) on one part of the weights (the
            top or a layer), its gradient scaled by ``clip``."""
            g = jax.tree.map(lambda a: a * clip, g)
            b1, b2 = opt["b1"], opt["b2"]
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * jnp.square(g_), v, g)
            bc1 = 1 - b1 ** t.astype(jnp.float32)
            bc2 = 1 - b2 ** t.astype(jnp.float32)
            p = jax.tree.map(
                lambda w, m_, v_: (
                    w.astype(jnp.float32)
                    - opt["lr"] * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["eps"])
                ).astype(w.dtype),
                p, m, v,
            )
            return p, m, v, _leaf_norms(g)

        top_shape, layer_shapes = split_layers(
            jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0))
        )
        top_placed, layer_placed = shardings(mesh, top_shape), shardings(mesh, layer_shapes[0])
        placed = shardings(mesh, {**top_shape, "layers": layer_shapes})
        self.fwd = jax.jit(fwd, out_shardings=seq_spec)
        # a layer's gradient placed as its weights: left to the compiler,
        # the attention kernels' come out whole on every chip
        self.bwd = jax.jit(
            bwd,
            out_shardings=(seq_spec, layer_placed, None, None) if mesh is not None else None,
        )
        self.head = jax.jit(head)
        self.embed = jax.jit(embed)
        self.embed_grad = jax.jit(embed_grad, donate_argnums=(0,))
        self.sq_sum = jax.jit(sq_sum)
        self.adam = jax.jit(adam, donate_argnums=(0, 1, 2))
        self.init_top = jax.jit(functools.partial(init_top, cfg), out_shardings=top_placed)
        self.init_layer = jax.jit(functools.partial(init_layer, cfg), out_shardings=layer_placed)
        # the moments placed as the weights: zeros left to the compiler
        # come out whole on every chip
        self.zeros = jax.jit(
            lambda p: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p),
            out_shardings=placed,
        )
        self.change = jax.jit(
            lambda p1, p0: _leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p1, p0))
        )

    def init(self, key):
        """The weights, placed, one layer at a time."""
        k_emb, ks = layer_keys(self.cfg, key)
        return {**self.init_top(k_emb), "layers": [self.init_layer(k) for k in ks]}

    def step(self, params, m, v, t, tokens, targets, weights, ctl, picks):
        """One training step, the backward and Adam layer by layer.
        Returns the new state, the loss, each layer's importance and
        kept-block norms, and the clipped gradient's leaf norms and
        table."""
        cfg, opt = self.cfg, self.cfg["optimizer"]
        n_tok = tokens.size
        x = self.embed(params["embed"]["table"], tokens)
        inputs = []
        for p, pk in zip(params["layers"], picks, strict=True):
            inputs.append(x)
            x = self.fwd(p, pk, ctl, x)
        xf = x.reshape(n_tok, -1)
        tf, wf = targets.reshape(-1), weights.reshape(-1)
        inv = 1.0 / float(np.sum(weights))
        blk = min(HEAD_TOKENS, n_tok)
        loss, g_scale, g_table, dxs = 0.0, 0.0, 0.0, []
        for lo in range(0, n_tok, blk):
            sl = slice(lo, lo + blk)
            lv, (gt, gs, dx) = self.head(
                params["embed"]["table"], params["final_norm"]["scale"], ctl,
                xf[sl], tf[sl], wf[sl] * inv,
            )
            loss, g_table, g_scale = loss + lv, g_table + gt, g_scale + gs
            dxs.append(dx)
        del x, xf
        dx = jnp.concatenate(dxs).reshape(tokens.shape + (-1,))
        grads, imps, kept = [None] * len(inputs), [None] * len(inputs), [None] * len(inputs)
        for i in reversed(range(len(inputs))):
            dx, grads[i], imps[i], kept[i] = self.bwd(
                params["layers"][i], picks[i], ctl, inputs[i], dx
            )
            inputs[i] = None
        g_top = {"embed": {"table": self.embed_grad(g_table, tokens, dx)}, "final_norm": {"scale": g_scale}}
        # clipped to the global norm of the whole gradient
        gn = jnp.sqrt(self.sq_sum(g_top) + sum(self.sq_sum(g) for g in grads))
        clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gn, 1e-12))
        t = t + 1
        (p_top, ps), (m_top, ms), (v_top, vs) = map(split_layers, (params, m, v))
        head = g_top["embed"]["table"] * clip
        p_top, m_top, v_top, norms = self.adam(p_top, m_top, v_top, g_top, clip, t)
        del g_top
        layer_norms = []
        for i in range(len(grads)):
            ps[i], ms[i], vs[i], n = self.adam(ps[i], ms[i], vs[i], grads[i], clip, t)
            grads[i] = None
            layer_norms.append(n)
        params, m, v = ({**top, "layers": ls} for top, ls in ((p_top, ps), (m_top, ms), (v_top, vs)))
        return (params, m, v, t), float(loss), imps, kept, {**norms, "layers": layer_norms}, head


def no_picks(cfg: dict, sel: dict) -> list:
    """Picks that keep no block: the reference keeps its own top blocks."""
    c_out = out_channels(cfg)
    one = {
        n: jnp.zeros((sel[n][0], -(-c_out[n] // sel[n][1]) // sel[n][0]), jnp.float32)
        for n in PROJECTIONS
    }
    return [one] * cfg["num_hidden_layers"]


def _by_key(cfg, sel, per_layer) -> dict:
    """``{reading key: [blocks]}`` from per-layer ``{projection: [shards, blocks]}``."""
    out = {}
    for key, (site, name, j, _) in site_keys(cfg, sel).items():
        i = int(site.split("/")[0][len("layer_"):])
        out[key] = np.asarray(per_layer[i][name][j])
    return out


def _from_keys(cfg, sel, flat: dict) -> list:
    """The inverse of :func:`_by_key`, for the picks the run under check
    gives."""
    keys = site_keys(cfg, sel)
    layers = [{n: [None] * sel[n][0] for n in PROJECTIONS} for _ in range(cfg["num_hidden_layers"])]
    for key, (site, name, j, _) in keys.items():
        i = int(site.split("/")[0][len("layer_"):])
        layers[i][name][j] = np.asarray(flat[key], np.float32)
    return [{n: jnp.asarray(np.stack(rows)) for n, rows in lay.items()} for lay in layers]


def make_step(config: dict, mix: dict, mesh=None) -> Step:
    return _make_step(json.dumps(config, sort_keys=True), json.dumps(mix, sort_keys=True), mesh)


@functools.lru_cache(maxsize=None)
def _make_step(config_json: str, mix_json: str, mesh):
    return Step(json.loads(config_json), json.loads(mix_json), mesh)


def readings(
    config: dict,
    mix: dict,
    key: jax.Array,
    batches,
    *,
    mesh=None,
    precision: str = "float32",
    fault: str = "",
    follow=None,
) -> dict:
    """Losses of each step over ``batches`` (``(tokens, targets)``), each
    leaf's clipped gradient norm and the embedding table's clipped
    gradient at the first step, each leaf's change over all the steps,
    and per step and reading key (:func:`site_keys`) the block norms of
    the weight gradient (``kept``) and every block's importance.

    ``follow`` gives each step's picks: ``{reading key: [blocks] 0/1}``,
    the blocks that the run under check kept; without it every site
    keeps its own top blocks. Traced at 'highest' matmul precision: on a
    TPU a float32 contraction at the default precision takes one
    bfloat16 pass.
    """
    step = make_step(config, mix, mesh)
    cfg, sel = step.cfg, step.sel
    ctl = jnp.asarray(control(precision, fault))
    losses, imp, kept, grad_norms, head = [], [], [], None, None
    with jax.default_matmul_precision("highest"):
        params = step.init(key)
        m = step.zeros(params)
        v = step.zeros(params)
        t = jnp.zeros((), jnp.int32)
        for i, (tokens, targets) in enumerate(batches):
            picks = _from_keys(cfg, sel, follow[i]) if follow else no_picks(cfg, sel)
            w = token_weights(*np.shape(tokens), fault)
            (params, m, v, t), loss, im, kn, norms, hg = step.step(
                params, m, v, t, jnp.asarray(tokens), jnp.asarray(targets),
                jnp.asarray(w), ctl, picks,
            )
            losses.append(loss)
            imp.append(_by_key(cfg, sel, jax.device_get(im)))
            kept.append(_by_key(cfg, sel, jax.device_get(kn)))
            if grad_norms is None:
                grad_norms, head = flat(norms), np.asarray(jax.device_get(hg))
            del hg
        del m, v
        change = flat(over_layers(step.change, params, step.init(key)))
    keep = {}
    if float(mix["drop_rate"]) > 0.0:
        keep = {k: sel[name][2] for k, (_, name, _, _) in site_keys(cfg, sel).items()}
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "head_grads": {"embed/table": head},
        "change_norms": change,
        "kept": kept,
        "importance": imp,
        "keep_blocks": keep,
    }


def flat(tree) -> dict[str, float]:
    """``{leaf name: value}`` of a tree of scalars."""
    return {k: float(v) for k, v in leaf_names(jax.device_get(tree)).items()}
