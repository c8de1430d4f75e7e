"""``sparse_conv2d``: convolution with ssProp channel-sparse backward.

Forward is ``jax.lax.conv_general_dilated`` (NCHW / OIHW, matching the
paper's tensor layout). Backward delegates to the shared channel-sparse
engine (:mod:`repro.core.backward`), which applies the paper's Fig. 1(a)
pipeline; this module supplies only the conv linear algebra:

* **full / mask-mode contraction** — the VJP of the conv itself,
* **gathered contraction** — the VJP of the conv restricted to the kept
  output channels, which XLA lowers to transposed convs with
  ``C_out' = K`` (exactly the (1-D) FLOPs saving of Eq. 9).

Every conv site that drops a block takes the gathered contraction,
``use_pallas`` or not: the conv offers the engine no Pallas lowering.
On a TPU v5e XLA's gathered conv beat the Pallas conv kernels
(``kernels/ops.py::conv_dx_fused`` / ``conv_dw_fused_scatter`` and the
im2col ``dx_gathered`` / ``dw_gathered_scatter``) at every ResNet-18
site that drops a block, on CIFAR and ImageNet shapes alike.

Grouped convs select a balanced top-k per group (the engine's shard
mechanism): a gathered grouped conv stays well-formed only when every
group keeps the same channel count. ``bwd_dtype`` and ``tp_shards``
behave as in ``sparse_dense``.
"""
from __future__ import annotations

from collections.abc import Sequence
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backward, sparsity
from repro.core.policy import SsPropPolicy

# frozen, so safe to share as the signature default
_DEFAULT_POLICY = SsPropPolicy()

_DN = ("NCHW", "OIHW", "NCHW")


def _norm_pair(v) -> tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    return tuple(v)


def _conv(x, w, stride, padding, dilation, groups):
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=stride,
        padding=padding,
        rhs_dilation=dilation,
        dimension_numbers=_DN,
        feature_group_count=groups,
    )


class _ConvOp(backward.ChannelSparseOp):
    """Conv adapter: NCHW dY, OIHW dW (output channels on axis 0)."""

    channel_axis = 1
    dw_channel_axis = 0

    def __init__(self, x, w, stride, padding, dilation, groups, policy):
        super().__init__(policy)
        self.x = x
        self.w = w
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.c_out = w.shape[0]

    def selection_shards(self, policy: SsPropPolicy) -> int:
        return sparsity.selection_shards(self.c_out, policy, self.groups)

    def _vjp(self, w, dy_eff):
        """VJP of the conv (over cast operands) applied to ``dy_eff``."""
        x, w = self._cast(self.x), self._cast(w)
        _, vjp = jax.vjp(
            lambda x_, w_: _conv(
                x_, w_, self.stride, self.padding, self.dilation, self.groups
            ),
            x,
            w,
        )
        return vjp(dy_eff.astype(jnp.result_type(x.dtype, w.dtype)))

    def contract_full(self, dy_eff):
        return self._vjp(self.w, dy_eff)

    def _one_sided_vjp(self, dy_eff, wrt_x: bool, w=None):
        """VJP w.r.t. a single operand — the mixed sparsify_dx/dw paths
        ask for one gradient; differentiating only that operand avoids
        the discarded-half contraction outside jit. ``w`` defaults to
        the full filters (dense side); the gathered sides pass the
        kept-channel restriction."""
        x, w = self._cast(self.x), self._cast(self.w if w is None else w)
        conv = lambda x_, w_: _conv(
            x_, w_, self.stride, self.padding, self.dilation, self.groups
        )
        if wrt_x:
            _, vjp = jax.vjp(lambda x_: conv(x_, w), x)
        else:
            _, vjp = jax.vjp(lambda w_: conv(x, w_), w)
        return vjp(dy_eff.astype(jnp.result_type(x.dtype, w.dtype)))[0]

    def dx_full(self, dy_eff):
        return self._one_sided_vjp(dy_eff, wrt_x=True)

    def dw_full(self, dy_eff):
        return self._one_sided_vjp(dy_eff, wrt_x=False)

    def contract_gathered_dx(self, dy_k, sel):
        w_k = self.take_kept(self.w, sel, 0)
        return self._one_sided_vjp(dy_k, wrt_x=True, w=w_k)

    def contract_gathered_dw(self, dy_k, sel):
        w_k = self.take_kept(self.w, sel, 0)
        return self._one_sided_vjp(dy_k, wrt_x=False, w=w_k)

    def contract_gathered(self, dy_k, sel):
        # VJP of the conv restricted to the kept output channels — the
        # transposed convs XLA emits have C_out' = K, i.e. shrunk FLOPs.
        # Balanced per-group selection keeps kept channel j in group
        # j // k_loc, so feature_group_count survives the restriction.
        w_k = self.take_kept(self.w, sel, 0)
        return self._vjp(w_k, dy_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _sparse_conv2d(policy, has_bias, stride, padding, dilation, groups, x, w, b, key32):
    y = _conv(x, w, stride, padding, dilation, groups)
    if has_bias:
        y = y + b[None, :, None, None]
    return y


def _fwd(policy, has_bias, stride, padding, dilation, groups, x, w, b, key32):
    y = _sparse_conv2d(policy, has_bias, stride, padding, dilation, groups, x, w, b, key32)
    return y, (x, w, key32)


def _bwd(policy: SsPropPolicy, has_bias, stride, padding, dilation, groups, res, dy):
    x, w, key32 = res
    c_out = w.shape[0]
    op = _ConvOp(x, w, stride, padding, dilation, groups, policy)
    dx, dw, db = backward.channel_sparse_backward(
        policy, op, dy, key32=key32, has_bias=has_bias
    )
    db_out = db.astype(dy.dtype) if has_bias else jnp.zeros((c_out,), dy.dtype)
    return (
        dx.astype(x.dtype),
        dw.astype(w.dtype),
        db_out,
        np.zeros(key32.shape, dtype=jax.dtypes.float0),
    )


_sparse_conv2d.defvjp(_fwd, _bwd)

_DUMMY_KEY = np.zeros((2,), dtype=np.uint32)


def sparse_conv2d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    stride: int | Sequence[int] = 1,
    padding: str | int | Sequence[tuple[int, int]] = 0,
    dilation: int | Sequence[int] = 1,
    groups: int = 1,
    policy: SsPropPolicy = _DEFAULT_POLICY,
    key: jax.Array | None = None,
) -> jax.Array:
    """2-D convolution (NCHW) with ssProp scheduled-sparse backward.

    Args:
      x: ``[B, C_in, H, W]`` input.
      w: ``[C_out, C_in // groups, Kh, Kw]`` filters (OIHW).
      b: optional ``[C_out]`` bias.
      stride / padding / dilation / groups: as in any DL framework; the
        paper's simplifying assumptions (p=0, d=1, g=1) are *not* baked in.
      policy: ssProp policy.
      key: PRNG key for ``selection="random"``.
    """
    stride = _norm_pair(stride)
    dilation = _norm_pair(dilation)
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    elif isinstance(padding, str):
        pass
    else:
        padding = tuple(tuple(p) for p in padding)
    has_bias = b is not None
    key32 = (
        jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
        if key is not None
        else jnp.asarray(_DUMMY_KEY)
    )
    if b is None:
        b = jnp.zeros((w.shape[0],), dtype=x.dtype)
    return _sparse_conv2d(
        policy, has_bias, stride, padding, dilation, groups, x, w, b, key32
    )
