"""Introspectable grid / BlockSpec descriptions of every Pallas kernel.

Each kernel in :mod:`repro.kernels.gathered_matmul` and
:mod:`repro.kernels.paged_attention` builds its ``pl.pallas_call`` from
a :class:`KernelSpec` returned by one of the ``*_spec`` constructors
below — and the static checker (:mod:`repro.analysis.pallas_check`)
evaluates the *same* spec objects over the full grid to prove in-bounds
access, block-shape divisibility and VMEM footprint, and to emulate HBM
traffic. Because kernel and checker consume one spec object, the two
cannot drift: an index-map change is automatically re-checked.

A spec is purely structural — grid, operand shapes, block shapes, index
maps, scratch buffers. Index maps have exactly the arity Pallas expects
(grid coordinates, plus the scalar-prefetch ref last when
``num_scalar_prefetch == 1``) and use only arithmetic/indexing, so the
checker can call them with plain Python ints and a NumPy array for the
prefetch operand.
"""
from __future__ import annotations

from collections.abc import Callable
import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BlockSpecInfo:
    """One operand's blocking: full shape, block shape, index map.

    ``index_map`` takes the grid coordinates (plus the scalar-prefetch
    array when the kernel uses one) and returns the *block* index per
    dimension — element offset = block index × block extent, exactly
    Pallas' ``BlockSpec`` contract. ``itemsize`` is the operand's bytes
    per element (for traffic/VMEM accounting).
    """

    name: str
    array_shape: tuple[int, ...]
    block_shape: tuple[int, ...]
    index_map: Callable
    itemsize: int = 4

    @property
    def block_elems(self) -> int:
        return math.prod(self.block_shape)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A kernel's full launch geometry.

    ``grid`` iterates sequentially on TPU with the *last* axis
    fastest-varying; a block whose index map returns the same indices on
    consecutive steps is fetched once and revisited in VMEM (the
    revisit-elision the traffic emulator models). ``scratch`` lists
    fp32 VMEM scratch shapes.
    """

    name: str
    grid: tuple[int, ...]
    in_specs: tuple[BlockSpecInfo, ...]
    out_specs: tuple[BlockSpecInfo, ...]
    num_scalar_prefetch: int = 0
    scratch: tuple[tuple[int, ...], ...] = ()

    @property
    def grid_size(self) -> int:
        return math.prod(self.grid)

    def grid_spec(self):
        """The ``pl.pallas_call`` grid spec this object describes."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        in_specs = [
            pl.BlockSpec(i.block_shape, i.index_map) for i in self.in_specs
        ]
        out_specs = [
            pl.BlockSpec(o.block_shape, o.index_map) for o in self.out_specs
        ]
        out = out_specs[0] if len(out_specs) == 1 else out_specs
        if self.num_scalar_prefetch or self.scratch:
            return dict(
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=self.num_scalar_prefetch,
                    grid=self.grid,
                    in_specs=in_specs,
                    out_specs=out,
                    scratch_shapes=[
                        pltpu.VMEM(s, jnp.float32) for s in self.scratch
                    ],
                )
            )
        return dict(grid=self.grid, in_specs=in_specs, out_specs=out)


# ----------------------------------------------------------------------
# gathered matmuls
# ----------------------------------------------------------------------


def dx_gathered_spec(
    m: int, n: int, d_in: int, kb: int, *, block_size: int = 128,
    bm: int = 128, bn: int = 128, itemsize: int = 4,
) -> KernelSpec:
    """dX[M, D_in] = Σ_kb dY[:, blk] @ W[:, blk]^T (see gathered_matmul)."""
    return KernelSpec(
        name="dx_gathered",
        grid=(m // bm, d_in // bn, kb),
        in_specs=(
            BlockSpecInfo(
                "dy", (m, n), (bm, block_size),
                lambda i, j, k, idx: (i, idx[k]), itemsize,
            ),
            BlockSpecInfo(
                "w", (d_in, n), (bn, block_size),
                lambda i, j, k, idx: (j, idx[k]), itemsize,
            ),
        ),
        out_specs=(
            BlockSpecInfo(
                "dx", (m, d_in), (bm, bn), lambda i, j, k, idx: (i, j), 4
            ),
        ),
        num_scalar_prefetch=1,
    )


def dw_gathered_spec(
    m: int, n: int, d_in: int, kb: int, *, block_size: int = 128,
    bm: int = 128, bk_m: int = 128, itemsize: int = 4,
) -> KernelSpec:
    """Compact dW[D_in, KB*bs] = X^T @ dY[:, kept]."""
    return KernelSpec(
        name="dw_gathered",
        grid=(d_in // bm, kb, m // bk_m),
        in_specs=(
            BlockSpecInfo(
                "x", (m, d_in), (bk_m, bm),
                lambda i, j, s, idx: (s, i), itemsize,
            ),
            BlockSpecInfo(
                "dy", (m, n), (bk_m, block_size),
                lambda i, j, s, idx: (s, idx[j]), itemsize,
            ),
        ),
        out_specs=(
            BlockSpecInfo(
                "dw", (d_in, kb * block_size), (bm, block_size),
                lambda i, j, s, idx: (i, j), 4,
            ),
        ),
        num_scalar_prefetch=1,
    )


# ----------------------------------------------------------------------
# fused-im2col conv backward
# ----------------------------------------------------------------------


def conv_dw_fused_spec(
    *, b: int, h_pad: int, w_pad: int, groups: int, cg: int, h_out: int,
    w_out: int, c_pad: int, kh_dim: int, kw_dim: int, stride, dilation,
    kb: int, block_size: int = 128, itemsize: int = 4,
) -> KernelSpec:
    """Compact conv dW ``[Kh, Kw, Cg, KB*bs]`` with fused patch gather.

    The image operand's index map holds the im2col contract checked
    against ``docs/kernels.md``: grid step ``(kh, j, s)`` reads padded
    image row ``(s // H_out) * H_pad + (s % H_out) * sh + kh * dh`` of
    the kept block's group, its columns split into ``sw`` phases
    (``W_pad`` rounded up to a multiple of ``sw``).
    """
    sh, sw = stride
    dh, _ = dilation
    m2 = b * h_out
    bpg = (c_pad // block_size) // groups
    w_ph = -(-w_pad // sw)
    return KernelSpec(
        name="conv_dw_fused",
        grid=(kh_dim, kb, m2),
        in_specs=(
            BlockSpecInfo(
                "xg", (b * h_pad, groups, sw, w_ph, cg), (1, 1, sw, w_ph, cg),
                lambda kh, j, s, idx: (
                    (s // h_out) * h_pad + (s % h_out) * sh + kh * dh,
                    idx[j] // bpg,
                    0,
                    0,
                    0,
                ),
                itemsize,
            ),
            BlockSpecInfo(
                "dy2r", (m2, w_out, c_pad), (1, w_out, block_size),
                lambda kh, j, s, idx: (s, 0, idx[j]), itemsize,
            ),
        ),
        out_specs=(
            BlockSpecInfo(
                "dw", (kh_dim, kw_dim, cg, kb * block_size),
                (1, kw_dim, cg, block_size),
                lambda kh, j, s, idx: (kh, 0, 0, j), 4,
            ),
        ),
        num_scalar_prefetch=1,
    )


def conv_dx_fused_spec(
    *, b: int, h_pad: int, w_pad: int, groups: int, cg: int, h_out: int,
    w_out: int, c_pad: int, kh_dim: int, kw_dim: int, stride, dilation,
    kb: int, block_size: int = 128, itemsize: int = 4,
) -> KernelSpec:
    """Padded-image conv dX with fused col2im scatter.

    The cotangent map inverts the dW map (clipped to a valid row — the
    kernel body masks out-of-range taps with ``pl.when``); the compact
    filter's map is *constant*, so the whole ``[Kh, Kw, Cg, KB*bs]``
    operand is fetched into VMEM exactly once across the row sweep.
    """
    sh, _ = stride
    dh, _ = dilation
    m2 = b * h_out
    bpg = (c_pad // block_size) // groups
    return KernelSpec(
        name="conv_dx_fused",
        grid=(b * h_pad, kb, kh_dim),
        in_specs=(
            BlockSpecInfo(
                "dy2r", (m2, w_out, c_pad), (1, w_out, block_size),
                lambda s, j, kh, idx: (
                    (s // h_pad) * h_out
                    + jnp.clip((s % h_pad - kh * dh) // sh, 0, h_out - 1),
                    0,
                    idx[j],
                ),
                itemsize,
            ),
            BlockSpecInfo(
                "w2k", (kh_dim, kw_dim, cg, kb * block_size),
                (kh_dim, kw_dim, cg, kb * block_size),
                lambda s, j, kh, idx: (0, 0, 0, 0), itemsize,
            ),
        ),
        out_specs=(
            BlockSpecInfo(
                "dxp", (b * h_pad, groups, w_pad, cg), (1, 1, w_pad, cg),
                lambda s, j, kh, idx: (s, idx[j] // bpg, 0, 0), 4,
            ),
        ),
        num_scalar_prefetch=1,
    )


# ----------------------------------------------------------------------
# importance / plain matmul
# ----------------------------------------------------------------------


def importance_spec(
    m: int, n: int, *, bm: int = 256, bn: int = 128, itemsize: int = 4
) -> KernelSpec:
    """imp[1, N] = Σ_row-blocks |dY| / M."""
    return KernelSpec(
        name="importance",
        grid=(n // bn, m // bm),
        in_specs=(
            BlockSpecInfo(
                "dy", (m, n), (bm, bn), lambda j, s: (s, j), itemsize
            ),
        ),
        out_specs=(
            BlockSpecInfo("imp", (1, n), (1, bn), lambda j, s: (0, j), 4),
        ),
    )


def matmul_spec(
    m: int, k: int, n: int, *, bm: int = 128, bn: int = 128, bk: int = 128,
    itemsize: int = 4,
) -> KernelSpec:
    """A[M, K] @ B[K, N] -> [M, N], MXU-tiled."""
    return KernelSpec(
        name="matmul",
        grid=(m // bm, n // bn, k // bk),
        in_specs=(
            BlockSpecInfo(
                "a", (m, k), (bm, bk), lambda i, j, s: (i, s), itemsize
            ),
            BlockSpecInfo(
                "b", (k, n), (bk, bn), lambda i, j, s: (s, j), itemsize
            ),
        ),
        out_specs=(
            BlockSpecInfo("out", (m, n), (bm, bn), lambda i, j, s: (i, j), 4),
        ),
    )


# ----------------------------------------------------------------------
# paged attention
# ----------------------------------------------------------------------


def paged_attention_spec(
    *, b: int, s: int, h: int, d: int, n_pages: int, bs_pg: int, kvh: int,
    nb: int, itemsize: int = 4,
) -> KernelSpec:
    """Decode attention over the K/V page pool via the block table.

    Grid ``(B, NB)``: batch row × logical block; the K/V maps read
    physical page ``tbl[b * NB + j]`` — the in-bounds proof over the
    full grid is exactly the "tables always address a real page" claim
    (the wrapper clips defensively; the checker proves the clip is a
    no-op for well-formed tables). Queries and output are group-major
    ``[B, KV, S*G, D]`` and query positions per row ``[B, S*G, 1]``, so
    every block spans its array's trailing two dims (the TPU tiling
    rule holds at any S and G).
    """
    sg = s * (h // kvh)
    return KernelSpec(
        name="paged_attention",
        grid=(b, nb),
        in_specs=(
            BlockSpecInfo(
                "q", (b, kvh, sg, d), (1, kvh, sg, d),
                lambda bi, j, tbl: (bi, 0, 0, 0), itemsize,
            ),
            BlockSpecInfo(
                "k_pool", (n_pages, bs_pg, kvh, d), (1, bs_pg, kvh, d),
                lambda bi, j, tbl: (tbl[bi * nb + j], 0, 0, 0), itemsize,
            ),
            BlockSpecInfo(
                "v_pool", (n_pages, bs_pg, kvh, d), (1, bs_pg, kvh, d),
                lambda bi, j, tbl: (tbl[bi * nb + j], 0, 0, 0), itemsize,
            ),
            BlockSpecInfo(
                "qpos", (b, sg, 1), (1, sg, 1),
                lambda bi, j, tbl: (bi, 0, 0), 4,
            ),
        ),
        out_specs=(
            BlockSpecInfo(
                "out", (b, kvh, sg, d), (1, kvh, sg, d),
                lambda bi, j, tbl: (bi, 0, 0, 0), 4,
            ),
        ),
        num_scalar_prefetch=1,
        scratch=((kvh, sg), (kvh, sg), (kvh, sg, d)),
    )
