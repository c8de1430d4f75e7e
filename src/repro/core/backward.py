"""The channel-sparse backward engine (paper Fig. 1(a), one implementation).

Both ``sparse_dense`` and ``sparse_conv2d`` used to carry their own copy
of the ssProp backward pipeline; they now delegate to
:func:`channel_sparse_backward`, which owns every op-independent stage:

  1. ``bwd_dtype`` casting of the output cotangent,
  2. importance → policy-driven channel/block selection (including the
     ragged-tail ``valid`` mask and shard-balanced selection for TP /
     grouped convs),
  3. the ``mask_mode`` oracle (same selection, materialized as a mask
     over a full-size contraction),
  4. the gather of kept channels and the scatter of compact dW/db back
     into full-size zero buffers (``.add``-based, so clamped tail
     duplicates cannot overwrite the last real channel),
  5. routing to the Pallas gathered kernels when the op can lower itself
     to the canonical 2-D form (``use_pallas`` + block granularity).

A site whose selection would keep every channel skips stages 2-5
(:func:`repro.core.sparsity.keeps_every_channel`, decided from the
static channel count, policy and shard count): block granularity keeps
whole 128-channel blocks, so at drop rate 0.8 a 64- or 128-channel conv
keeps its only block. Its backward is then the dense one, and it takes
the dense contraction (the op's VJP, as at drop rate 0) rather than
selecting an identity and paying a gathered kernel's per-block layout
for it. Sites that drop a block keep every route below.

Ops plug in through :class:`ChannelSparseOp`, providing only their
linear algebra: the full-size contraction, the shrunk (gathered)
contraction, and optionally a :class:`CanonicalForm` — the im2col-style
``X2 [M, D_flat] / W2 [D_flat, C_out] / dY2 [M, C_out]`` view that the
Pallas ``dx_gathered`` / ``dw_gathered_scatter`` kernels consume — and a
TP fast path for comm-free sharded gathers.

Selection consistency is the engine's core guarantee: mask mode and
gather mode share one :class:`repro.core.sparsity.Selection` per call,
so gather-mode output equals the mask-mode oracle to accumulation
tolerance across every configuration (the property the parity test grid
pins down).
"""
from __future__ import annotations

from collections.abc import Callable
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import sparsity
from repro.core.policy import SsPropPolicy

# Name scopes of the engine's two stages. Every operation of a stage
# carries its scope in its HLO ``op_name``, under the calling site's
# scope, so a device trace splits each site's backward into selection
# (importance, block importance, top-k) and contraction (the gathered or
# masked dX / dW products and their scatter).
SELECT_SCOPE = "ssprop_select"
CONTRACT_SCOPE = "ssprop_contract"
# Nested in the contraction scope at sites whose selection keeps every
# channel, which contract densely and select nothing.
ALL_KEPT_SCOPE = "ssprop_all_kept"


@dataclasses.dataclass
class CanonicalForm:
    """An op lowered to the 2-D matmul form the Pallas kernels speak.

    ``x2 [M, D_flat]``, ``w2 [D_flat, C_out]``, ``dy2 [M, C_out]`` with
    rows of ``x2``/``dy2`` aligned (same (batch, position) ordering).
    ``dx_from`` / ``dw_from`` lift the canonical gradients — dX2
    ``[M, D_flat]`` and full-size dW2 ``[D_flat, C_out]`` — back to the
    op's native shapes (dense: reshape; conv: col2im / OIHW reshape).
    """

    x2: jax.Array
    w2: jax.Array
    dy2: jax.Array
    dx_from: Callable[[jax.Array], jax.Array]
    dw_from: Callable[[jax.Array], jax.Array]


class ChannelSparseOp:
    """Adapter protocol: the op-specific linear algebra.

    Attributes:
      c_out: number of output channels (the sparsified axis).
      channel_axis: position of the channel axis in ``dy``.
      dw_channel_axis: position of the output-channel axis in ``dw``.

    ``__init__`` installs the shared ``bwd_dtype`` machinery: ``_acc``
    (the accumulation dtype) and ``_cast`` (casts contraction operands
    into it when ``bwd_dtype`` is set, identity otherwise — natural
    promotion is left alone for the default fp32 backward).
    """

    c_out: int
    channel_axis: int
    dw_channel_axis: int

    def __init__(self, policy: SsPropPolicy):
        self.policy = policy
        self._acc = _acc_dtype(policy)
        self._cast = (
            (lambda a: a.astype(self._acc)) if policy.bwd_dtype else (lambda a: a)
        )

    def selection_shards(self, policy: SsPropPolicy) -> int:
        """How many contiguous channel groups selection must balance over
        (1 = global top-k). Ops fold structural constraints (conv groups)
        and the policy's TP degree into this."""
        return 1

    def contract_full(self, dy_eff: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(dX, dW) from a full-size (possibly masked) cotangent."""
        raise NotImplementedError

    def dx_full(self, dy_eff: jax.Array) -> jax.Array:
        """Dense dX alone (``sparsify_dx=False`` path). The default rides
        on ``contract_full``; under jit the unused dW branch is DCE'd."""
        return self.contract_full(dy_eff)[0]

    def dw_full(self, dy_eff: jax.Array) -> jax.Array:
        """Dense dW alone (``sparsify_dw=False`` path)."""
        return self.contract_full(dy_eff)[1]

    def contract_gathered(
        self, dy_k: jax.Array, sel: sparsity.Selection
    ) -> tuple[jax.Array, jax.Array]:
        """(dX, compact dW) from the gathered cotangent ``dy_k`` (kept
        channels only, phantom slots already zeroed). The compact dW has
        ``sel.k`` channels on ``dw_channel_axis``; the engine scatters."""
        raise NotImplementedError

    def contract_gathered_dx(self, dy_k: jax.Array, sel) -> jax.Array:
        """Gathered dX alone (mixed ``sparsify_dw=False`` path). The
        default discards the dW half; under jit that half is DCE'd."""
        return self.contract_gathered(dy_k, sel)[0]

    def contract_gathered_dw(self, dy_k: jax.Array, sel) -> jax.Array:
        """Gathered compact dW alone (mixed ``sparsify_dx=False`` path)."""
        return self.contract_gathered(dy_k, sel)[1]

    def canonical(self, dy_eff: jax.Array) -> CanonicalForm | None:
        """The 2-D lowering for the Pallas gathered kernels, or None when
        the op cannot (or should not) lower itself."""
        return None

    def fused_backward(
        self, dy_eff: jax.Array, sel: sparsity.Selection, sdx: bool, sdw: bool
    ) -> tuple[jax.Array, jax.Array] | None:
        """Optional fully-fused Pallas path: (dX, dW) in native shapes and
        accumulation dtype, or None to fall through to the canonical-form
        kernels. Checked first in the Pallas branch — ops that can fuse
        their data-layout transform into the kernels' index maps (conv
        im2col) skip the materialized canonical buffers entirely."""
        return None

    def tp_contract(
        self, dy_eff: jax.Array, sel: sparsity.Selection
    ) -> tuple[jax.Array, jax.Array] | None:
        """Optional comm-free sharded fast path: (dX, full dW) from the
        per-shard selection, or None to use the generic gather path."""
        return None


def scatter_channels(
    compact: jax.Array, idx: jax.Array, c: int, axis: int
) -> jax.Array:
    """Scatter a compact per-kept-channel tensor into full-size zeros.

    Accumulating (``.add``): duplicate indices — the clamped phantoms of
    a ragged block tail, whose values the engine has already zeroed —
    contribute nothing instead of overwriting.
    """
    axis = axis % compact.ndim
    shape = list(compact.shape)
    shape[axis] = c
    sl: list = [slice(None)] * compact.ndim
    sl[axis] = idx
    return jnp.zeros(shape, compact.dtype).at[tuple(sl)].add(compact)


def _acc_dtype(policy: SsPropPolicy):
    return jnp.bfloat16 if policy.bwd_dtype == "bfloat16" else jnp.float32


def _wrap_key(policy: SsPropPolicy, key32) -> jax.Array | None:
    if policy.selection == "random" and key32 is not None:
        return jax.random.wrap_key_data(key32.astype(jnp.uint32))
    return None


def channel_sparse_backward(
    policy: SsPropPolicy,
    op: ChannelSparseOp,
    dy: jax.Array,
    *,
    key32: jax.Array | None = None,
    has_bias: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Run the shared ssProp backward pipeline for one op.

    Returns ``(dX, dW, db)`` in accumulation dtype (callers cast back to
    their parameter dtypes); ``db`` is None when ``has_bias`` is False.
    """
    ca = op.channel_axis % dy.ndim
    c = op.c_out
    reduce_axes = tuple(a for a in range(dy.ndim) if a != ca)
    dy_eff = dy.astype(_acc_dtype(policy)) if policy.bwd_dtype else dy
    sdx, sdw = policy.sparsify_dx, policy.sparsify_dw

    if not policy.active or not (sdx or sdw):
        with jax.named_scope(CONTRACT_SCOPE):
            dx, dw = op.contract_full(dy_eff)
        db = dy_eff.sum(axis=reduce_axes) if has_bias else None
        return dx, dw, db

    n_shards = op.selection_shards(policy)
    if sparsity.keeps_every_channel(c, policy, n_shards):
        # Selection would keep every channel (at 0.8, the one 128-channel
        # block of a 64- or 128-channel site), so the product is the dense
        # one: XLA's VJP, as at drop rate 0, with no selection and none of
        # the gathered-block routes. Mask mode's mask would be all ones.
        with jax.named_scope(CONTRACT_SCOPE), jax.named_scope(ALL_KEPT_SCOPE):
            dx, dw = op.contract_full(dy_eff)
        db = dy_eff.sum(axis=reduce_axes) if has_bias else None
        return dx, dw, db

    key = _wrap_key(policy, key32)
    with jax.named_scope(SELECT_SCOPE):
        sel = sparsity.select(
            dy_eff,
            policy,
            channel_axis=ca,
            n_shards=n_shards,
            key=key,
        )

    if policy.mask_mode:
        # Reference semantics: identical selection, zeroed channels,
        # full-size contraction. The oracle every other path must match.
        # A gradient whose sparsify_* flag is off sees the raw cotangent.
        with jax.named_scope(CONTRACT_SCOPE):
            mask = sparsity.keep_mask(dy.shape, sel.idx, channel_axis=ca, dtype=dy_eff.dtype)
            dy_m = dy_eff * mask
            dx = op.dx_full(dy_m if sdx else dy_eff)
            dw = op.dw_full(dy_m if sdw else dy_eff)
        db = (dy_m if sdw else dy_eff).sum(axis=reduce_axes) if has_bias else None
        return dx, dw, db

    db = None
    if has_bias:
        # db follows the dW side (bias is a weight). With sparsify_dw
        # off it stays dense; otherwise: clamped phantom slots always
        # point into the kept tail block, so the plain keep-mask is
        # correct even when sel.valid exists.
        db = dy_eff.sum(axis=reduce_axes)
        if sdw:
            km = sparsity.keep_mask((c,), sel.idx, channel_axis=0, dtype=dy_eff.dtype)
            db = db * km

    with jax.named_scope(CONTRACT_SCOPE):
        dx, dw = _contract_selected(policy, op, dy_eff, sel, ca, sdx, sdw)
    return dx, dw, db


def _contract_selected(
    policy: SsPropPolicy,
    op: ChannelSparseOp,
    dy_eff: jax.Array,
    sel: sparsity.Selection,
    ca: int,
    sdx: bool,
    sdw: bool,
) -> tuple[jax.Array, jax.Array]:
    """(dX, dW) at the selected channels, by the first route that takes
    the op: the TP fast path, the fused Pallas kernels, the canonical
    Pallas kernels, else the gathered XLA contraction and scatter."""
    c = op.c_out
    if sel.shard_idx is not None and sdx and sdw:
        fast = op.tp_contract(dy_eff, sel)
        if fast is not None:
            return fast

    if (
        policy.use_pallas
        and policy.granularity == "block"
        and sel.block_idx is not None
    ):
        fused = op.fused_backward(dy_eff, sel, sdx, sdw)
        if fused is not None:
            return fused
        can = op.canonical(dy_eff)
        if can is not None:
            from repro.kernels import ops as kops

            if sdx:
                dx2 = kops.dx_gathered(can.dy2, can.w2, sel.block_idx, policy.block_size)
            else:
                dx2 = jnp.matmul(can.dy2, can.w2.T)
            if sdw:
                dw2 = kops.dw_gathered_scatter(
                    can.x2, can.dy2, sel.block_idx, c, policy.block_size
                )
            else:
                dw2 = jnp.matmul(can.x2.T, can.dy2)
            return can.dx_from(dx2), can.dw_from(dw2)

    dy_k = jnp.take(dy_eff, sel.idx, axis=ca)
    if sel.valid is not None:
        vshape = [1] * dy_eff.ndim
        vshape[ca] = sel.k
        dy_k = dy_k * sel.valid.reshape(vshape).astype(dy_k.dtype)
    if sdx and sdw:
        dx, dw_compact = op.contract_gathered(dy_k, sel)
    elif sdx:
        dx = op.contract_gathered_dx(dy_k, sel)
        dw_compact = None
    else:
        dx = op.dx_full(dy_eff)
        dw_compact = op.contract_gathered_dw(dy_k, sel)
    if sdw:
        dw = scatter_channels(dw_compact, sel.idx, c, op.dw_channel_axis)
    else:
        dw = op.dw_full(dy_eff)
    return dx, dw
