"""Jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples, backend detection (TPU → compiled
kernel, anything else → ``interpret=True`` so CPU CI exercises the same
kernel body), and the scatter of the compact dW back into the full
weight-gradient buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import gathered_matmul as gm
from repro.kernels import paged_attention as pa


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _interpret_blocks(block_size: int) -> bool:
    """``_interpret()``, refusing channel blocks Mosaic cannot tile.

    A compiled kernel's channel block rides the 128-wide lane axis, so on
    the TPU ``block_size`` must be a multiple of 128; interpret mode (CPU
    demos and tests) takes any size.
    """
    interpret = _interpret()
    if not interpret and block_size % 128:
        raise ValueError(
            f"Pallas block_size={block_size} cannot lower on TPU: channel "
            "blocks must be a multiple of the 128-wide lane axis"
        )
    return interpret


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_size",))
def dx_gathered(dy, w, block_idx, block_size: int = 128):
    """dX[M, D_in] = dY[:, kept] @ W[:, kept]^T, gather fused in-kernel."""
    m, n = dy.shape
    d_in = w.shape[0]
    dy_p = _pad_to(_pad_to(dy, 0, 128), 1, block_size)
    w_p = _pad_to(_pad_to(w, 0, 128), 1, block_size)
    out = gm.dx_gathered(
        dy_p, w_p, block_idx, block_size=block_size,
        interpret=_interpret_blocks(block_size),
    )
    return out[:m, :d_in]


@functools.partial(jax.jit, static_argnames=("block_size", "n_out"))
def dw_gathered_scatter(x, dy, block_idx, n_out: int, block_size: int = 128):
    """Full dW[D_in, N]: compact kernel output scattered into zeros."""
    m, d_in = x.shape
    x_p = _pad_to(_pad_to(x, 0, 128), 1, 128)
    dy_p = _pad_to(_pad_to(dy, 0, 128), 1, block_size)
    compact = gm.dw_gathered(
        x_p, dy_p, block_idx, block_size=block_size,
        interpret=_interpret_blocks(block_size),
    )  # [D_in_pad, KB*block_size]
    compact = compact[:d_in]
    kb = block_idx.shape[0]
    dw = jnp.zeros((d_in, -(-n_out // block_size), block_size), jnp.float32)
    dw = dw.at[:, block_idx, :].set(compact.reshape(d_in, kb, block_size))
    return dw.reshape(d_in, -1)[:, :n_out]


def _dy_rows(dy, block_size):
    """NCHW cotangent -> ``[B*H_out, W_out, C_pad]`` row layout."""
    b, c_out, h_out, w_out = dy.shape
    dy2r = dy.transpose(0, 2, 3, 1).reshape(b * h_out, w_out, c_out)
    return _pad_to(dy2r, 2, block_size)


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "dilation", "groups", "block_size"),
)
def conv_dw_fused_scatter(
    x, dy, block_idx, *, kh, kw, stride, padding, dilation, groups, block_size=128
):
    """Canonical conv dW2 ``[Cg*Kh*Kw, C_out]`` with fused patch gather.

    The ``[M, C_in*Kh*Kw]`` im2col buffer is never built: the kernel's
    index maps read padded image rows in place (``gathered_matmul.
    conv_dw_fused``). Compact kernel output is scattered into full-size
    zeros over the kept output-channel blocks.
    """
    b, c_in, h, w_dim = x.shape
    c_out, h_out = dy.shape[1], dy.shape[2]
    cg = c_in // groups
    (ph0, ph1), (pw0, pw1) = padding
    sw = stride[1]
    h_pad = h + ph0 + ph1
    w_ph = -(-(w_dim + pw0 + pw1) // sw)
    c_pad = c_out + (-c_out) % block_size
    dy2r = _dy_rows(dy, block_size)
    # NCHW -> group-blocked padded rows split into sw column phases,
    # [B*H_pad, G, sw, W_ph, Cg]: padded column i*sw + p lands at [p, i]
    # (the zero tail rounds W_pad up to sw*W_ph)
    xp = jnp.pad(
        x, ((0, 0), (0, 0), padding[0], (pw0, sw * w_ph - w_dim - pw0))
    )
    xg = (
        xp.transpose(0, 2, 3, 1)
        .reshape(b, h_pad, w_ph, sw, groups, cg)
        .transpose(0, 1, 4, 3, 2, 5)
        .reshape(b * h_pad, groups, sw, w_ph, cg)
    )
    compact = gm.conv_dw_fused(
        xg, dy2r, block_idx, kh_dim=kh, kw_dim=kw, stride=stride,
        dilation=dilation, h_out=h_out, block_size=block_size,
        interpret=_interpret_blocks(block_size),
    )  # [Kh, Kw, Cg, KB*bs]
    kb = block_idx.shape[0]
    d_flat = cg * kh * kw
    compact = compact.transpose(2, 0, 1, 3).reshape(d_flat, kb * block_size)
    dw = jnp.zeros((d_flat, c_pad // block_size, block_size), jnp.float32)
    dw = dw.at[:, block_idx, :].set(compact.reshape(d_flat, kb, block_size))
    return dw.reshape(d_flat, c_pad)[:, :c_out]


@functools.partial(
    jax.jit,
    static_argnames=("hw", "stride", "padding", "dilation", "groups", "block_size"),
)
def conv_dx_fused(
    dy, w, block_idx, *, hw, stride, padding, dilation, groups, block_size=128
):
    """Conv dX ``[B, C_in, H, W]`` with fused col2im scatter.

    ``hw`` is the static (H, W) of the input. The kernel accumulates on
    the zero-padded image (``gathered_matmul.conv_dx_fused``); the
    padding border is sliced off here. The kept filter blocks are
    gathered *here* (filters are tiny next to activations) so the kernel
    can hold the whole compact filter in VMEM across the row sweep.
    """
    b = dy.shape[0]
    h, w_dim = hw
    cg = w.shape[1]
    (ph0, ph1), (pw0, pw1) = padding
    h_pad, w_pad = h + ph0 + ph1, w_dim + pw0 + pw1
    dy2r = _dy_rows(dy, block_size)
    wfull = _pad_to(w.transpose(2, 3, 1, 0), 3, block_size)  # [Kh,Kw,Cg,C_pad]
    kh, kw = wfull.shape[:2]
    nb = wfull.shape[3] // block_size
    w2k = jnp.take(
        wfull.reshape(kh, kw, cg, nb, block_size), block_idx, axis=3
    ).reshape(kh, kw, cg, -1)  # compact [Kh,Kw,Cg,KB*bs]
    dxp = gm.conv_dx_fused(
        dy2r, w2k, block_idx, b=b, h_pad=h_pad, w_pad=w_pad, groups=groups,
        stride=stride, dilation=dilation, block_size=block_size,
        interpret=_interpret_blocks(block_size),
    )  # [B*H_pad, G, W_pad, Cg]
    dx = (
        dxp.reshape(b, h_pad, groups, w_pad, cg)
        .transpose(0, 2, 4, 1, 3)
        .reshape(b, groups * cg, h_pad, w_pad)
    )
    return dx[:, :, ph0 : ph0 + h, pw0 : pw0 + w_dim]


@jax.jit
def paged_attention(q, k_pool, v_pool, block_tables, qpos):
    """Per-slot causal attention reading K/V pages in place.

    q ``[B,S,H,D]``, pools ``[n_pages, bs, KV, D]``, block_tables
    ``[B, NB]``, qpos ``[B, S]`` -> ``[B, S, H, D]`` in q.dtype. The
    kernel-side contract (grid, addressing, online softmax) lives in
    :mod:`repro.kernels.paged_attention`.
    """
    out = pa.paged_attention(
        q, k_pool, v_pool, block_tables, qpos, interpret=_interpret()
    )
    return out.astype(q.dtype)


@jax.jit
def importance(dy):
    """Per-channel mean |dY| over all leading axes. dy [..., N] -> [N]."""
    n = dy.shape[-1]
    dy2 = dy.reshape(-1, n)
    m = dy2.shape[0]
    dy_p = _pad_to(_pad_to(dy2, 0, 256), 1, 128)
    # zero padding is |.|-neutral; rescale the mean to the true M.
    out = gm.importance(dy_p, interpret=_interpret())
    return out[:n] * (dy_p.shape[0] / m)


@jax.jit
def matmul(a, b):
    """Padded MXU-tiled matmul."""
    m, k = a.shape
    n = b.shape[1]
    a_p = _pad_to(_pad_to(a, 0, 128), 1, 128)
    b_p = _pad_to(_pad_to(b, 0, 128), 1, 128)
    return gm.matmul(a_p, b_p, interpret=_interpret())[:m, :n]
