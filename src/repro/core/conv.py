"""``sparse_conv2d``: convolution with ssProp channel-sparse backward.

Forward is ``jax.lax.conv_general_dilated`` (NCHW / OIHW, matching the
paper's tensor layout). Backward delegates to the shared channel-sparse
engine (:mod:`repro.core.backward`), which applies the paper's Fig. 1(a)
pipeline; this module supplies only the conv linear algebra:

* **full / mask-mode contraction** — the VJP of the conv itself,
* **gathered contraction** — the VJP of the conv restricted to the kept
  output channels, which XLA lowers to transposed convs with
  ``C_out' = K`` (exactly the (1-D) FLOPs saving of Eq. 9),
* **fused Pallas backward** — the default Pallas route
  (``fuse_im2col=True``): ``kernels/ops.py::conv_dx_fused`` /
  ``conv_dw_fused_scatter`` extract im2col patches inside the kernels'
  HBM→VMEM index maps, so the ``[M, C_in*Kh*Kw]`` patch buffer is never
  materialized. Grouped convs ride the same kernels in block-diagonal
  form whenever per-group channel counts are block-aligned.
* **canonical (im2col) lowering** — ``kernels/im2col.py`` columnizes the
  conv so block-granular selection routes through the same Pallas
  ``dx_gathered`` / ``dw_gathered_scatter`` kernels as ``sparse_dense``
  when ``use_pallas=True, granularity="block"``. With ``fuse_im2col``
  on this is only the A/B baseline; it materializes ``X2`` in HBM.

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
        w_k = jnp.take(self.w, sel.idx, axis=0)
        return self._one_sided_vjp(dy_k, wrt_x=True, w=w_k)

    def contract_gathered_dw(self, dy_k, sel):
        w_k = jnp.take(self.w, sel.idx, axis=0)
        return self._one_sided_vjp(dy_k, wrt_x=False, w=w_k)

    def contract_gathered(self, dy_k, sel):
        # VJP of the conv restricted to the kept output channels — the
        # transposed convs XLA emits have C_out' = K, i.e. shrunk FLOPs.
        # Balanced per-group selection keeps kept channel j in group
        # j // k_loc, so feature_group_count survives the restriction.
        w_k = jnp.take(self.w, sel.idx, axis=0)
        return self._vjp(w_k, dy_k)

    def _explicit_padding(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """Resolve string padding to explicit per-dim (lo, hi) pairs.

        The fused kernels address the zero-padded image directly, so they
        need numbers; ``padtype_to_pads`` wants the *effective* (dilated)
        filter extent."""
        if isinstance(self.padding, str):
            kh, kw = self.w.shape[2:]
            eff = tuple((k - 1) * d + 1 for k, d in zip((kh, kw), self.dilation, strict=True))
            pads = jax.lax.padtype_to_pads(
                self.x.shape[2:], eff, self.stride, self.padding
            )
            return tuple(tuple(p) for p in pads)
        return tuple(tuple(p) for p in self.padding)

    def fused_backward(self, dy_eff, sel, sdx, sdw):
        if not self.policy.fuse_im2col:
            return None
        if self.w.shape[2] == self.w.shape[3] == 1:
            # 1x1: im2col is a reshape/slice, there is no patch buffer
            # to fuse away — the canonical kernels are the cheaper path.
            return None
        bs = self.policy.block_size
        c_out = self.c_out
        if self.groups > 1 and c_out % (self.groups * bs) != 0:
            # block-diagonal routing needs whole blocks per group
            return None
        # The traffic model is the routing authority: fuse only when the
        # kernels' per-(tap × kept-block) re-fetches move fewer bytes
        # than the [M, N] patch buffers they eliminate. All inputs are
        # static, so this folds away under jit.
        from repro.core import flops as F

        bt, _, h_out, w_out = dy_eff.shape
        model = functools.partial(
            F.conv_backward_bytes_policy,
            bt, h_out, w_out, self.x.shape[1], c_out, self.w.shape[2],
            self.policy, groups=self.groups,
        )
        if model(fused=True) >= model(fused=False):
            return None
        from repro.kernels import ops as kops

        pads = self._explicit_padding()
        x, w = self._cast(self.x), self._cast(self.w)
        dy_eff = dy_eff.astype(jnp.result_type(x.dtype, w.dtype))
        nb = -(-c_out // bs)
        # dense side of a mixed sparsify_dx/dw policy: every block kept
        dense_idx = jnp.arange(nb, dtype=sel.block_idx.dtype)
        kh, kw = self.w.shape[2:]
        common = dict(
            stride=self.stride, padding=pads, dilation=self.dilation,
            groups=self.groups, block_size=bs,
        )
        dx = kops.conv_dx_fused(
            dy_eff, w, sel.block_idx if sdx else dense_idx,
            hw=self.x.shape[2:], **common,
        )
        dw2 = kops.conv_dw_fused_scatter(
            x, dy_eff, sel.block_idx if sdw else dense_idx, kh=kh, kw=kw, **common,
        )  # [Cg*Kh*Kw, C_out] with (c, kh, kw) row order -> OIHW
        dw = dw2.T.reshape(c_out, self.w.shape[1], kh, kw)
        return dx.astype(self._acc), dw.astype(self._acc)

    def canonical(self, dy_eff):
        if self.groups != 1:
            return None
        from repro.kernels import im2col

        c_out, _, kh, kw = self.w.shape
        x2, col2im, _ = im2col.conv_patches(
            self._cast(self.x), kh, kw, self.stride, self.padding, self.dilation
        )
        return backward.CanonicalForm(
            x2=x2,
            w2=self._cast(im2col.flatten_filters(self.w)),
            dy2=im2col.flatten_grad(dy_eff),
            dx_from=col2im,
            dw_from=lambda dw2: im2col.unflatten_filter_grad(dw2, self.w.shape),
        )


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
