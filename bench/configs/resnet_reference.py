"""Plain reference of the ResNet classifier step, in jax.numpy and float32.

It imports nothing of the program. It makes its own weights from the seed
with the same arithmetic as the program's initializer (Kaiming-normal
convs and head from one split of the key, BatchNorm scale 1 and bias 0),
and states ssProp's backward directly: at every conv, the output
gradient's per-channel importance (mean |dY| over batch and space),
averaged over channel blocks, the top blocks kept, the others zeroed, and
the conv's plain VJP taken of what is left. BatchNorm on batch
statistics, softmax cross-entropy over the batch, then Adam.

Blocks whose importance ties to rounding may be kept on one side and
dropped on the other. So each step takes, per conv site, a ``pick``: the
blocks that the run under check kept there, read from its gradient. Where
a pick keeps as many blocks as the site does, the reference keeps those,
and reports its own importance of every block, so that the comparison
holds the pick itself to a limit (``selection_gap``); otherwise it keeps
its own top blocks.

``precision`` gives the reference (``"float32"``, every contraction at
'highest') or a lower-precision step to hold the check against:
``"bfloat16"`` computes the forward and backward in bfloat16 (float32
master weights and optimizer); ``"fp8"`` rounds both operands of every
contraction, forward and backward, to float8_e4m3fn at a per-tensor scale
(amax to 448) and accumulates in float32. The configuration's contractions
run in bfloat16 (float32 operands at the TPU's default precision, one
bfloat16 pass), so ``"fp8"`` is the control. ``fault`` plants a fault:
``"half_batch"`` takes the loss over the first half of the batch only,
``"wrong_block"`` keeps the least important blocks, ``"dw_x2"`` doubles
every conv's weight gradient.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

_DN = ("NCHW", "OIHW", "NCHW")
BN_EPS = 1e-5


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------
def _conv_w(key, c_out, c_in, k):
    w = jax.random.normal(key, (c_out, c_in, k, k), jnp.float32)
    return {"w": w * math.sqrt(2.0 / (c_in * k * k))}


def _bn(c):
    return {
        "scale": jnp.ones((c,), jnp.float32),
        "bias": jnp.zeros((c,), jnp.float32),
        "mean": jnp.zeros((c,), jnp.float32),
        "var": jnp.ones((c,), jnp.float32),
    }


def _strides(config):
    return [
        2 if (b == 0 and si > 0) else 1
        for si, n in enumerate(config["stages"])
        for b in range(n)
    ]


def init_params(config: dict, key: jax.Array) -> dict:
    keys = iter(jax.random.split(key, 64))
    widths = config["widths"]
    c_img = config["image"][0]
    p = {
        "stem": _conv_w(next(keys), widths[0], c_img, config["stem"]["kernel"]),
        "stem_bn": _bn(widths[0]),
        "blocks": [],
    }
    c_in = widths[0]
    for si, (n, w) in enumerate(zip(config["stages"], widths, strict=True)):
        for b in range(n):
            stride = 2 if (b == 0 and si > 0) else 1
            ks = jax.random.split(next(keys), 3)
            blk = {
                "conv1": _conv_w(ks[0], w, c_in, 3),
                "bn1": _bn(w),
                "conv2": _conv_w(ks[1], w, w, 3),
                "bn2": _bn(w),
            }
            if stride != 1 or c_in != w:
                blk["down_conv"] = _conv_w(ks[2], w, c_in, 1)
                blk["down_bn"] = _bn(w)
            p["blocks"].append(blk)
            c_in = w
    p["head"] = {
        "w": jax.random.normal(next(keys), (c_in, config["n_classes"]), jnp.float32)
        * math.sqrt(2.0 / c_in),
        "b": jnp.zeros((config["n_classes"],), jnp.float32),
    }
    return p


# ----------------------------------------------------------------------
# ssProp conv: dense forward, top-block masked output gradient backward
# ----------------------------------------------------------------------
def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)), dimension_numbers=_DN
    )


def _block(sparsity) -> int:
    granularity, block_size, _ = sparsity
    return 1 if granularity == "channel" else block_size


def keep_blocks(c: int, sparsity) -> int:
    """How many of a ``c``-channel site's blocks the backward keeps."""
    nb = -(-c // _block(sparsity))
    return max(1, int(round((1.0 - sparsity[2]) * nb)))


def importance(dy, sparsity):
    """``[nb]`` mean ``|dY|`` of each block of output channels."""
    bs = _block(sparsity)
    c = dy.shape[1]
    nb = -(-c // bs)
    imp = jnp.mean(jnp.abs(dy).astype(jnp.float32), axis=(0, 2, 3))
    return jnp.pad(imp, (0, nb * bs - c)).reshape(nb, bs).mean(1)


def fp8(a):
    """``a`` rounded to float8_e4m3fn at a per-tensor scale (amax -> 448)."""
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s


def _same(a):
    return a


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def sparse_conv(x, w, pick, stride, pad, sparsity, rnd, fault):
    return _conv(rnd(x), rnd(w), stride, pad)


def _sparse_fwd(x, w, pick, stride, pad, sparsity, rnd, fault):
    return _conv(rnd(x), rnd(w), stride, pad), (x, w, pick)


def _sparse_bwd(stride, pad, sparsity, rnd, fault, res, dy):
    """VJP of the kept blocks; ``pick``'s cotangent carries the blocks'
    importance out to the caller."""
    x, w, pick = res
    imp = importance(dy, sparsity)
    if sparsity[2] > 0.0:
        kb = keep_blocks(dy.shape[1], sparsity)
        _, top = jax.lax.top_k(-imp if fault == "wrong_block" else imp, kb)
        own = jnp.zeros_like(imp).at[top].set(1.0)
        keep = jnp.where(jnp.sum(pick) == kb, pick, own)
        keep = jnp.repeat(keep, _block(sparsity))[: dy.shape[1]]
        dy = dy * keep.astype(dy.dtype)[None, :, None, None]
    _, vjp = jax.vjp(lambda a, b: _conv(a, b, stride, pad), rnd(x), rnd(w))
    dx, dw = vjp(rnd(dy))
    if fault == "dw_x2":
        dw = 2 * dw
    return dx, dw, imp


sparse_conv.defvjp(_sparse_fwd, _sparse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rounded_dot(h, w, rnd):
    return rnd(h) @ rnd(w)


def _dot_fwd(h, w, rnd):
    return rnd(h) @ rnd(w), (h, w)


def _dot_bwd(rnd, res, dy):
    h, w = res
    dy = rnd(dy)
    return dy @ rnd(w).T, rnd(h).T @ dy


rounded_dot.defvjp(_dot_fwd, _dot_bwd)

_MODES = {"float32": (jnp.float32, None), "bfloat16": (jnp.bfloat16, None), "fp8": (jnp.float32, fp8)}


def conv(p, pick, x, stride, pad, sparsity, mode, fault):
    dtype, rnd = _MODES[mode]
    w = p["w"].astype(dtype)
    if rnd is None and sparsity[2] <= 0.0 and fault != "dw_x2":
        return _conv(x, w, stride, pad)
    return sparse_conv(x, w, pick, stride, pad, sparsity, rnd or _same, fault)


def batchnorm(p, x, mode):
    dtype = _MODES[mode][0]
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    inv = jax.lax.rsqrt(var + BN_EPS)
    y = (x - mean[None, :, None, None]) * inv[None, :, None, None]
    scale, bias = p["scale"].astype(dtype), p["bias"].astype(dtype)
    return y * scale[None, :, None, None] + bias[None, :, None, None]


def conv_sites(config: dict) -> dict[str, int]:
    """``{key path of a conv's weight: its output channels}``."""
    widths = config["widths"]
    sites = {"['stem']['w']": widths[0]}
    c_in, i = widths[0], 0
    for si, (n, w) in enumerate(zip(config["stages"], widths, strict=True)):
        for b in range(n):
            sites[f"['blocks'][{i}]['conv1']['w']"] = w
            sites[f"['blocks'][{i}]['conv2']['w']"] = w
            if (b == 0 and si > 0) or c_in != w:
                sites[f"['blocks'][{i}]['down_conv']['w']"] = w
            c_in, i = w, i + 1
    return sites


def forward(config, params, picks, x, sparsity, mode, fault=""):
    dtype, rnd = _MODES[mode]
    stem = config["stem"]
    k = stem["kernel"]

    def site(p, name, h, stride, pad):
        return conv(p, picks[name], h, stride, pad, sparsity, mode, fault)

    h = site(params["stem"], "['stem']['w']", x, stem["stride"], k // 2)
    h = jax.nn.relu(batchnorm(params["stem_bn"], h, mode))
    if stem["maxpool"]:
        h = -jax.lax.reduce_window(
            -h, jnp.inf, jax.lax.min, (1, 1, 3, 3), (1, 1, 2, 2), "SAME"
        )
    for i, (blk, stride) in enumerate(zip(params["blocks"], _strides(config), strict=True)):
        name = f"['blocks'][{i}]"
        y = site(blk["conv1"], f"{name}['conv1']['w']", h, stride, 1)
        y = jax.nn.relu(batchnorm(blk["bn1"], y, mode))
        y = batchnorm(blk["bn2"], site(blk["conv2"], f"{name}['conv2']['w']", y, 1, 1), mode)
        if "down_conv" in blk:
            h = site(blk["down_conv"], f"{name}['down_conv']['w']", h, stride, 0)
            h = batchnorm(blk["down_bn"], h, mode)
        h = jax.nn.relu(y + h)
    h = h.mean(axis=(2, 3))
    w, b = params["head"]["w"].astype(dtype), params["head"]["b"].astype(dtype)
    return (h @ w if rnd is None else rounded_dot(h, w, rnd)) + b


def loss_fn(config, sparsity, mode, fault, params, picks, x, y):
    logits = forward(config, params, picks, x.astype(_MODES[mode][0]), sparsity, mode, fault)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -logp[jnp.arange(x.shape[0]), y].mean()


# ----------------------------------------------------------------------
# Adam and the three-step readings
# ----------------------------------------------------------------------
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def block_norms(grads, sites: dict[str, int], block: int) -> dict:
    """``{site: [nb]}`` norm of each block of a conv's weight-gradient rows
    (output channels): a dropped block's rows are zero."""
    flat = {jax.tree_util.keystr(p): a for p, a in jax.tree_util.tree_flatten_with_path(grads)[0]}
    out = {}
    for name, c in sites.items():
        rows = jnp.sum(jnp.square(flat[name].astype(jnp.float32)), axis=(1, 2, 3))
        nb = -(-c // block)
        out[name] = jnp.sqrt(jnp.pad(rows, (0, nb * block - c)).reshape(nb, block).sum(1))
    return out


def make_step(config: dict, sparsity, precision: str = "float32", fault: str = ""):
    """The jitted reference step, built once per configuration and kind."""
    return _make_step(json.dumps(config, sort_keys=True), tuple(sparsity), precision, fault)


@functools.lru_cache(maxsize=None)
def _make_step(config_json: str, sparsity, precision: str, fault: str):
    config = json.loads(config_json)
    opt = config["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    sites, block = conv_sites(config), _block(sparsity)

    def step(params, m, v, t, x, y, picks):
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        loss, (g, imp) = jax.value_and_grad(
            lambda p, k: loss_fn(config, sparsity, precision, fault, p, k, x, y), argnums=(0, 1)
        )(params, picks)
        t = t + 1
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * jnp.square(g_), v, g)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
            params, m, v,
        )
        return params, m, v, t, loss, _leaf_norms(g), imp, block_norms(g, sites, block), g["head"]

    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _init_fn(config_json: str):
    return jax.jit(functools.partial(init_params, json.loads(config_json)))


@jax.jit
def _init_state(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return zeros, zeros, jnp.zeros((), jnp.int32)


@jax.jit
def _change_norms(p1, p0):
    return _leaf_norms(jax.tree.map(jnp.subtract, p1, p0))


def readings(
    config: dict,
    mix: dict,
    key: jax.Array,
    batches,
    *,
    precision: str = "float32",
    fault: str = "",
    follow=None,
    step=None,
) -> dict:
    """Losses of each step over ``batches``, each leaf's gradient norm at
    the first step and the head's first gradient, each leaf's change over
    all the steps, and per step and conv site the block norms of the
    weight gradient (``kept``) and every block's importance.

    ``follow`` gives each step's picks: ``{site: [nb] 0/1}``, the blocks
    that the run under check kept; without it every site keeps its own
    top blocks. Traced at
    'highest' matmul precision: on a TPU a float32 contraction at the
    default precision takes one bfloat16 pass. ``step`` reuses a
    :func:`make_step` that an earlier call built.
    """
    sparsity = (mix["granularity"], mix["block_size"], float(mix["drop_rate"]))
    if step is None:
        step = make_step(config, sparsity, precision, fault)
    sites = conv_sites(config)
    none = {n: jnp.zeros((-(-c // _block(sparsity)),), jnp.float32) for n, c in sites.items()}
    with jax.default_matmul_precision("highest"):
        params0 = _init_fn(json.dumps(config, sort_keys=True))(key)
        params = params0
        m, v, t = _init_state(params0)
        losses, grad_norms, head, kept, imp = [], None, None, [], []
        for i, (x, y) in enumerate(batches):
            picks = {n: jnp.asarray(follow[i][n], jnp.float32) for n in sites} if follow else none
            params, m, v, t, loss, gn, im, kn, hg = step(params, m, v, t, x, y, picks)
            losses.append(float(loss))
            kept.append(jax.device_get(kn))
            imp.append(jax.device_get(im))
            if grad_norms is None:
                grad_norms, head = gn, hg
        change = _change_norms(params, params0)
    keep = {}
    if sparsity[2] > 0.0:
        keep = {n: keep_blocks(c, sparsity) for n, c in sites.items()}
    return {
        "losses": losses,
        "grad_norms": flat(grad_norms),
        "head_grads": {f"['head']{k}": v for k, v in flat_arrays(head).items()},
        "change_norms": flat(change),
        "kept": kept,
        "importance": imp,
        "keep_blocks": keep,
    }


def flat_arrays(tree) -> dict:
    """``{key path: host array}`` of a tree."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): v for p, v in leaves}


def flat(tree) -> dict[str, float]:
    """``{key path: value}`` of a tree of scalars."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): float(v) for p, v in leaves}
