"""``sparse_dense``: matmul with ssProp channel-sparse backward.

Forward is a plain ``Y = X @ W (+ b)``. Backward implements the paper's
Fig. 1(a) pipeline generalized from conv to any linear operator
(the paper's stated extension path — DESIGN.md §4):

  1. importance[c] = mean |dY[..., c]| over all leading axes,
  2. keep top-K channels (or 128-channel blocks, TPU mode),
  3. dX = dY_kept @ W[:, kept]^T        (shrunk matmul, (1-D) FLOPs)
  4. dW[:, kept] = X^T @ dY_kept, dW[:, dropped] = 0
  5. db[kept]   = sum dY_kept,          db[dropped] = 0

The pipeline itself — selection, mask-mode oracle, ``bwd_dtype``
casting, TP-local selection, Pallas routing, compact-gradient scatter —
lives in :mod:`repro.core.backward`; this module only supplies the dense
linear algebra through a :class:`~repro.core.backward.ChannelSparseOp`
adapter. ``sparse_conv2d`` plugs into the same engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backward, sparsity
from repro.core.policy import SsPropPolicy

# frozen, so safe to share as the signature default
_DEFAULT_POLICY = SsPropPolicy()


def _float0_like(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


class _DenseOp(backward.ChannelSparseOp):
    """Canonical-form op: X2 [M, D_in] @ W [D_in, D_out]."""

    channel_axis = 1
    dw_channel_axis = 1
    block_slabs = False

    def __init__(self, x2: jax.Array, w: jax.Array, policy: SsPropPolicy):
        super().__init__(policy)
        self.x2 = x2
        self.w = w
        self.c_out = w.shape[1]

    def selection_shards(self, policy: SsPropPolicy) -> int:
        return sparsity.selection_shards(self.c_out, policy)

    def contract_full(self, dy_eff):
        return self.dx_full(dy_eff), self.dw_full(dy_eff)

    def dx_full(self, dy_eff):
        return jnp.matmul(dy_eff, self._cast(self.w.T))

    def dw_full(self, dy_eff):
        return jnp.matmul(self._cast(self.x2.T), dy_eff)

    def contract_gathered(self, dy_k, sel):
        return self.contract_gathered_dx(dy_k, sel), self.contract_gathered_dw(dy_k, sel)

    def contract_gathered_dx(self, dy_k, sel):
        w_k = self._cast(jnp.take(self.w, sel.idx, axis=1))
        if self.policy.use_pallas:
            from repro.kernels import ops as kops

            return kops.matmul(dy_k, w_k.T)
        return jnp.matmul(dy_k, w_k.T)          # shrunk: 2*M*K*D_in

    def contract_gathered_dw(self, dy_k, sel):
        x2 = self._cast(self.x2)
        if self.policy.use_pallas:
            from repro.kernels import ops as kops

            return kops.matmul(x2.T, dy_k)
        return jnp.matmul(x2.T, dy_k)           # shrunk: 2*M*D_in*K

    def canonical(self, dy_eff):
        return backward.CanonicalForm(
            x2=self._cast(self.x2),
            w2=self._cast(self.w),
            dy2=dy_eff,
            dx_from=lambda dx2: dx2,
            dw_from=lambda dw2: dw2,
        )

    def tp_contract(self, dy_eff, sel):
        # TP-local selection: gather stays on the shard-local channel
        # axis (take_along_axis), so GSPMD never all-gathers dY. The
        # contraction over (shard, kept) for dX reduces exactly like the
        # dense row-parallel matmul (one psum of [M, D_in]).
        m = dy_eff.shape[0]
        d_in = self.w.shape[0]
        s, c_loc = sel.n_shards, self.c_out // sel.n_shards
        dy3 = dy_eff.reshape(m, s, c_loc)
        dy_k = jnp.take_along_axis(dy3, sel.shard_idx[None], axis=2)  # [M, s, k]
        w3 = self.w.reshape(d_in, s, c_loc)
        w_k = jnp.take_along_axis(w3, sel.shard_idx[None], axis=2)  # [D_in, s, k]
        dx2 = jnp.einsum(
            "msk,dsk->md", dy_k, w_k.astype(dy_k.dtype),
            preferred_element_type=self._acc,
        )
        dw_k = jnp.einsum(
            "md,msk->dsk", self.x2.astype(dy_k.dtype), dy_k,
            preferred_element_type=self._acc,
        )  # [D_in, s, k]
        dw = (
            jnp.zeros((d_in, s, c_loc), dw_k.dtype)
            .at[:, jnp.arange(s)[:, None], sel.shard_idx]
            .set(dw_k)
            .reshape(d_in, self.c_out)
        )
        return dx2, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sparse_dense(policy: SsPropPolicy, has_bias: bool, x, w, b, key32):
    y = jnp.matmul(x, w)
    if has_bias:
        y = y + b
    return y


def _fwd(policy, has_bias, x, w, b, key32):
    return _sparse_dense(policy, has_bias, x, w, b, key32), (x, w, key32)


def _bwd(policy: SsPropPolicy, has_bias: bool, res, dy):
    x, w, key32 = res
    d_in, d_out = w.shape
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    op = _DenseOp(x.reshape(m, d_in), w, policy)
    dx2, dw, db = backward.channel_sparse_backward(
        policy, op, dy.reshape(m, d_out), key32=key32, has_bias=has_bias
    )
    dx = dx2.reshape(*lead, d_in).astype(x.dtype)
    dw = dw.astype(w.dtype)
    db_out = db.astype(dy.dtype) if has_bias else jnp.zeros((d_out,), dy.dtype)
    return dx, dw, db_out, _float0_like(key32)


_sparse_dense.defvjp(_fwd, _bwd)

_DUMMY_KEY = np.zeros((2,), dtype=np.uint32)


def sparse_dense(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    policy: SsPropPolicy = _DEFAULT_POLICY,
    key: jax.Array | None = None,
) -> jax.Array:
    """Linear layer with ssProp scheduled-sparse backward.

    Args:
      x: ``[..., D_in]`` activations.
      w: ``[D_in, D_out]`` weights.
      b: optional ``[D_out]`` bias.
      policy: the ssProp policy (drop rate already bucketed/static).
      key: PRNG key, only needed for ``selection="random"``.

    Returns:
      ``[..., D_out]`` output; backward follows the policy.
    """
    has_bias = b is not None
    key32 = (
        jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
        if key is not None
        else jnp.asarray(_DUMMY_KEY)
    )
    if b is None:
        b = jnp.zeros((w.shape[1],), dtype=x.dtype)
    return _sparse_dense(policy, has_bias, x, w, b, key32)
