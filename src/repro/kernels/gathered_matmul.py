"""Pallas TPU kernels: channel-block-gathered backward matmuls.

The TPU-native heart of ssProp (DESIGN.md §3.2): instead of materializing
a shrunk ``dY_kept`` in HBM, the kept-block indices ride in SMEM (scalar
prefetch) and the ``BlockSpec`` index maps address the kept 128-channel
blocks of ``dY`` / ``W`` directly during the HBM→VMEM copy. The gather is
thus free — the MXU only ever sees dense, 128-aligned tiles.

Kernels:
  * ``dx_gathered``  : dX[M, D_in]  = Σ_kb dY[:, blk] @ W[:, blk]^T
  * ``dw_gathered``  : dWk[D_in, K] = X^T @ dY[:, kept]   (compact out)
  * ``conv_dx_fused`` / ``conv_dw_fused``: the conv backward with the
    im2col patch extraction *fused into the index maps* — the kernels
    read padded image rows / cotangent rows straight from HBM and never
    materialize the ``[M, C_in*Kh*Kw]`` patch buffer. The dynamic
    spatial offset (``oh*sh + kh*dh``) lands on a leading block-size-1
    axis whose index map computes the row arithmetically from the grid
    coordinates; ``kw``/stride are static slices of the loaded VMEM
    row, pre-split into column phases so no slice is strided. Grouped
    convs ride the same kernels in block-diagonal form: operands carry
    an explicit group axis and the kept output block's group indexes it
    (``block_idx[j] // blocks_per_group``).
  * ``importance``   : imp[N]       = mean_M |dY|

Grid iteration on TPU is sequential over the last axis, so accumulation
into the revisited output block (init at step 0) is the standard pattern.
All accumulation is fp32 (``preferred_element_type``).

Every kernel's grid and BlockSpecs come from the matching ``*_spec``
constructor in :mod:`repro.kernels.specs` — the introspectable launch
geometry the static checker (:mod:`repro.analysis.pallas_check`) proves
in-bounds and traffic-models. Kernel and checker share one spec object,
so the addressing documented in ``docs/kernels.md`` cannot silently
drift from what runs.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from repro.kernels import specs


def mxu_dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """In-kernel ``dot_general`` accumulating in fp32.

    Mosaic accepts a contract precision only for two fp32 operands, so
    any other pair contracts at the default precision whatever
    ``jax_default_matmul_precision`` says (products of 16-bit operands
    are exact in fp32 anyway); an fp32 pair follows that setting.
    """
    both_f32 = a.dtype == b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, dims,
        precision=None if both_f32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


# ----------------------------------------------------------------------
# dX = dY[:, kept] @ W[:, kept]^T  — gather fused via scalar prefetch.
# ----------------------------------------------------------------------
def _dx_kernel(idx_ref, dy_ref, w_ref, out_ref, *, nk: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dy_blk = dy_ref[...]  # [bm, bk]   kept block of dY
    w_blk = w_ref[...]    # [bn, bk]   same kept block of W (D_in rows)
    out_ref[...] += mxu_dot(dy_blk, w_blk, (((1,), (1,)), ((), ())))


def dx_gathered(
    dy: jax.Array,
    w: jax.Array,
    block_idx: jax.Array,
    *,
    block_size: int = 128,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dX[M, D_in] from full dY[M, N], W[D_in, N], kept block_idx[KB].

    M, D_in must be multiples of (bm, bn) and N of block_size — callers
    (ops.py) pad. Output is fp32.
    """
    m, n = dy.shape
    d_in, n2 = w.shape
    assert n == n2, (n, n2)
    kb = block_idx.shape[0]
    assert m % bm == 0 and d_in % bn == 0 and n % block_size == 0

    spec = specs.dx_gathered_spec(
        m, n, d_in, kb, block_size=block_size, bm=bm, bn=bn,
        itemsize=dy.dtype.itemsize,
    )
    return pl.pallas_call(
        functools.partial(_dx_kernel, nk=kb),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((m, d_in), jnp.float32),
        interpret=interpret,
    )(block_idx, dy, w)


# ----------------------------------------------------------------------
# compact dW = X^T @ dY[:, kept] — output written compact [D_in, K].
# ----------------------------------------------------------------------
def _dw_kernel(idx_ref, x_ref, dy_ref, out_ref, *, nsteps: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x_blk = x_ref[...]    # [bk_m, bm]  rows of X, D_in cols
    dy_blk = dy_ref[...]  # [bk_m, bs]  kept channel block of dY
    out_ref[...] += mxu_dot(x_blk, dy_blk, (((0,), (0,)), ((), ())))


def dw_gathered(
    x: jax.Array,
    dy: jax.Array,
    block_idx: jax.Array,
    *,
    block_size: int = 128,
    bm: int = 128,
    bk_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Compact dW[D_in, KB*block_size] from X[M, D_in], dY[M, N].

    Column block j of the output corresponds to channel block
    ``block_idx[j]`` of the full dW; callers scatter it back.
    """
    m, d_in = x.shape
    m2, n = dy.shape
    assert m == m2
    kb = block_idx.shape[0]
    assert m % bk_m == 0 and d_in % bm == 0 and n % block_size == 0

    nsteps = m // bk_m
    spec = specs.dw_gathered_spec(
        m, n, d_in, kb, block_size=block_size, bm=bm, bk_m=bk_m,
        itemsize=x.dtype.itemsize,
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, nsteps=nsteps),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((d_in, kb * block_size), jnp.float32),
        interpret=interpret,
    )(block_idx, x, dy)


# ----------------------------------------------------------------------
# fused-im2col conv backward: patch extraction in the index maps.
#
# Layouts (prepared by ops.py):
#   xg   [B*H_pad, G, sw, W_ph, Cg] zero-padded input, group-blocked,
#                                  columns split into sw phases (padded
#                                  column i*sw + p at [p, i])
#   dy2r [B*H_out, W_out, C_pad]   cotangent rows, channels padded to
#                                  a block_size multiple
#   w2k  [Kh, Kw, Cg, C_pad]       filters, OIHW -> (kh, kw, c_in, c_out)
#
# The im2col row for output position (b, oh, ow) and tap (kh, kw) lives
# at padded-image row ``b*H_pad + oh*sh + kh*dh``, column ``ow*sw +
# kw*dw`` — the row part is pure index-map arithmetic on a block-size-1
# leading axis, the column part a static slice of one column phase of
# the loaded row. Nothing [M, C_in*Kh*Kw]-shaped ever exists in HBM.
# ----------------------------------------------------------------------
def _conv_dw_kernel(idx_ref, x_ref, dy_ref, out_ref, *, kw_dim, sw, dw_, w_out):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dyb = dy_ref[0]  # [W_out, bs] — cotangent row oh, kept block j
    for kw in range(kw_dim):
        # [W_out, Cg]: the (kh, kw) tap of every patch in padded image row
        # oh*sh + kh*dh. Column kw*dw + sw*i sits in column phase
        # (kw*dw) % sw at offset (kw*dw) // sw + i, so the strided tap is
        # a contiguous static slice (Mosaic lowers no strided slices of
        # 16-bit data, and none at all of loaded vector values).
        off, ph = divmod(kw * dw_, sw)
        xs = x_ref[0, 0, ph, off : off + w_out, :]
        out_ref[0, kw] += mxu_dot(xs, dyb, (((0,), (0,)), ((), ())))  # [Cg, bs]


def conv_dw_fused(
    xg: jax.Array,
    dy2r: jax.Array,
    block_idx: jax.Array,
    *,
    kh_dim: int,
    kw_dim: int,
    stride,
    dilation,
    h_out: int,
    block_size: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Compact conv dW with fused patch gather.

    Returns ``[Kh, Kw, Cg, KB*block_size]`` fp32 — tap-major compact
    weight gradient; column block j is output-channel block
    ``block_idx[j]``. Callers transpose to the canonical ``(c, kh, kw)``
    row order and scatter.
    """
    s_total, g, sw_ph, w_ph, cg = xg.shape
    m2, w_out, c_pad = dy2r.shape
    assert m2 % h_out == 0 and c_pad % block_size == 0
    b = m2 // h_out
    h_pad = s_total // b
    assert b * h_pad == s_total, (s_total, b, h_pad)
    kb = block_idx.shape[0]
    _, sw = stride
    _, dw_ = dilation
    assert sw_ph == sw, (xg.shape, stride)

    spec = specs.conv_dw_fused_spec(
        b=b, h_pad=h_pad, w_pad=sw * w_ph, groups=g, cg=cg, h_out=h_out,
        w_out=w_out, c_pad=c_pad, kh_dim=kh_dim, kw_dim=kw_dim,
        stride=stride, dilation=dilation, kb=kb, block_size=block_size,
        itemsize=xg.dtype.itemsize,
    )
    return pl.pallas_call(
        functools.partial(
            _conv_dw_kernel, kw_dim=kw_dim, sw=sw, dw_=dw_, w_out=w_out
        ),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct(
            (kh_dim, kw_dim, cg, kb * block_size), jnp.float32
        ),
        interpret=interpret,
    )(block_idx, xg, dy2r)


def _conv_dx_kernel(
    idx_ref, dy_ref, w_ref, out_ref, *, kw_dim, sh, sw, dh, dw_, h_out, h_pad,
    kbg, bs
):
    s = pl.program_id(0)
    j = pl.program_id(1)
    kh = pl.program_id(2)

    @pl.when((kh == 0) & (j % kbg == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Padded-image row s%h_pad receives tap kh from cotangent row oh
    # only when oh = (s%h_pad - kh*dh)/sh is a whole in-range number.
    oh_num = s % h_pad - kh * dh
    valid = (oh_num >= 0) & (oh_num < sh * h_out) & (oh_num % sh == 0)

    @pl.when(valid)
    def _acc():
        dyrow = dy_ref[0]  # [W_out, bs]
        for kw in range(kw_dim):
            wk = w_ref[kh, kw, :, pl.dslice(j * bs, bs)]  # [Cg, bs]
            part = mxu_dot(dyrow, wk, (((1,), (1,)), ((), ())))  # [W_out, Cg]
            w_out, cg = part.shape
            if sw == 1:
                out_ref[0, 0, dw_ * kw : dw_ * kw + w_out, :] += part
            else:
                # strided scatter: interleave sw-1 zero rows, then a
                # contiguous add at the kw tap's column offset
                spread = jnp.pad(part[:, None, :], ((0, 0), (0, sw - 1), (0, 0)))
                spread = spread.reshape(w_out * sw, cg)
                n = sw * (w_out - 1) + 1
                out_ref[0, 0, dw_ * kw : dw_ * kw + n, :] += spread[:n]


def conv_dx_fused(
    dy2r: jax.Array,
    w2k: jax.Array,
    block_idx: jax.Array,
    *,
    b: int,
    h_pad: int,
    w_pad: int,
    groups: int,
    stride,
    dilation,
    block_size: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Padded-image conv dX with fused col2im scatter.

    ``w2k [Kh, Kw, Cg, KB*block_size]`` is the *compact* filter — kept
    output-channel blocks only, gathered by the caller (a tiny jnp take:
    filters are orders of magnitude smaller than activations). Its
    BlockSpec index map is constant, so the whole compact filter is
    fetched into VMEM exactly once and reused across every image row —
    re-fetching it per row would swamp the traffic the fusion saves.

    Returns ``dxp [B*H_pad, G, W_pad, Cg]`` fp32 — the input gradient on
    the zero-padded image; callers slice the padding off and restore
    NCHW. ``block_idx`` still rides in SMEM for the cotangent gather and
    the output group routing (pass ``arange(NB)`` with the full filter
    for the dense side of a mixed policy).
    """
    m2, w_out, c_pad = dy2r.shape
    kh_dim, kw_dim, cg, kbbs = w2k.shape
    assert m2 % b == 0
    h_out = m2 // b
    kb = block_idx.shape[0]
    assert kbbs == kb * block_size, (w2k.shape, kb, block_size)
    assert kb % groups == 0, (kb, groups)
    kbg = kb // groups
    sh, sw = stride
    dh, dw_ = dilation

    spec = specs.conv_dx_fused_spec(
        b=b, h_pad=h_pad, w_pad=w_pad, groups=groups, cg=cg, h_out=h_out,
        w_out=w_out, c_pad=c_pad, kh_dim=kh_dim, kw_dim=kw_dim,
        stride=stride, dilation=dilation, kb=kb, block_size=block_size,
        itemsize=dy2r.dtype.itemsize,
    )
    return pl.pallas_call(
        functools.partial(
            _conv_dx_kernel, kw_dim=kw_dim, sh=sh, sw=sw, dh=dh, dw_=dw_,
            h_out=h_out, h_pad=h_pad, kbg=kbg, bs=block_size,
        ),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((b * h_pad, groups, w_pad, cg), jnp.float32),
        interpret=interpret,
    )(block_idx, dy2r, w2k)


# ----------------------------------------------------------------------
# importance: imp[N] = mean_M |dY| — fp32 tree of row-block partials.
# ----------------------------------------------------------------------
def _imp_kernel(dy_ref, out_ref, *, m_total: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    blk = jnp.abs(dy_ref[...].astype(jnp.float32))
    out_ref[...] += jnp.sum(blk, axis=0, keepdims=True) / m_total


def importance(
    dy: jax.Array,
    *,
    bm: int = 256,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Per-channel importance mean |dY| over rows: dy[M, N] -> [N] f32."""
    m, n = dy.shape
    assert m % bm == 0 and n % bn == 0
    spec = specs.importance_spec(m, n, bm=bm, bn=bn, itemsize=dy.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_imp_kernel, m_total=m),
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(dy)
    return out[0]


# ----------------------------------------------------------------------
# plain blocked matmul (used for the per-channel-granularity fallback
# where the gather cannot be block-fused; also a tuning baseline).
# ----------------------------------------------------------------------
def _mm_kernel(a_ref, b_ref, out_ref):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += mxu_dot(a_ref[...], b_ref[...], (((1,), (0,)), ((), ())))


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """A[M, K] @ B[K, N] -> [M, N] f32, MXU-tiled."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    spec = specs.matmul_spec(
        m, k, n, bm=bm, bn=bn, bk=bk, itemsize=a.dtype.itemsize
    )
    return pl.pallas_call(
        _mm_kernel,
        **spec.grid_spec(),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a, b)
