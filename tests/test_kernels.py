"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gathered_matmul as gm
from repro.kernels import ops, ref

SHAPES_DX = [
    # (M, N, D_in, kept_blocks)
    (128, 256, 128, [0]),
    (256, 512, 384, [0, 2, 3]),
    (200, 512, 130, [1, 3]),  # non-multiples exercise padding
    (64, 128, 64, [0]),
]


@pytest.mark.parametrize("m,n,d,blocks", SHAPES_DX)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dx_gathered(m, n, d, blocks, dtype):
    k = jax.random.PRNGKey(0)
    dy = jax.random.normal(k, (m, n), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, n), dtype)
    bidx = jnp.asarray(blocks, jnp.int32)
    out = ops.dx_gathered(dy, w, bidx)
    expect = ref.dx_gathered_ref(dy, w, bidx, 128)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("m,n,d,blocks", SHAPES_DX)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dw_gathered_scatter(m, n, d, blocks, dtype):
    k = jax.random.PRNGKey(2)
    x = jax.random.normal(k, (m, d), dtype)
    dy = jax.random.normal(jax.random.PRNGKey(3), (m, n), dtype)
    bidx = jnp.asarray(blocks, jnp.int32)
    out = ops.dw_gathered_scatter(x, dy, bidx, n)
    cols = ref.expand_block_idx(bidx, 128)
    expect = (
        jnp.zeros((d, n), jnp.float32)
        .at[:, cols]
        .set(ref.dw_gathered_ref(x, dy, bidx, 128))
    )
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol * 10)
    # dropped blocks must be exactly zero
    dropped = sorted(set(range(n // 128)) - set(blocks))
    for b in dropped:
        assert np.abs(np.asarray(out)[:, b * 128 : (b + 1) * 128]).sum() == 0


@pytest.mark.parametrize("m,n", [(256, 128), (300, 130), (512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_importance_kernel(m, n, dtype):
    dy = jax.random.normal(jax.random.PRNGKey(4), (m, n), dtype)
    out = ops.importance(dy)
    expect = ref.importance_ref(dy)
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (200, 384, 130), (64, 256, 512)])
def test_matmul_kernel(m, k, n):
    a = jax.random.normal(jax.random.PRNGKey(5), (m, k))
    b = jax.random.normal(jax.random.PRNGKey(6), (k, n))
    np.testing.assert_allclose(
        ops.matmul(a, b), ref.matmul_ref(a, b), rtol=1e-5, atol=1e-3
    )


def test_kernel_blockspec_grid_shapes():
    """Direct (unpadded) kernel invocation at several block sizes."""
    for bm, bn in [(128, 128), (256, 128)]:
        m, n, d = 512, 512, 512
        dy = jax.random.normal(jax.random.PRNGKey(7), (m, n))
        w = jax.random.normal(jax.random.PRNGKey(8), (d, n))
        bidx = jnp.asarray([0, 3], jnp.int32)
        out = gm.dx_gathered(dy, w, bidx, bm=bm, bn=bn, interpret=True)
        np.testing.assert_allclose(
            out, ref.dx_gathered_ref(dy, w, bidx, 128), rtol=1e-5, atol=1e-3
        )


# --- fused-im2col conv kernels vs the framework conv VJP -------------

_DN = ("NCHW", "OIHW", "NCHW")
_FUSED_GEOMS = [
    # (stride, padding, dilation, groups)
    (1, 1, 1, 1),
    (2, 1, 1, 1),
    (1, 0, 2, 1),
    (1, 1, 1, 2),
    (2, 1, 2, 2),
]


def _conv_fused_case(stride, padding, dilation, groups, c_out=16, bs=4):
    c_in, k = 6, 3
    x = jax.random.normal(jax.random.PRNGKey(10), (2, c_in, 8, 8))
    w = jax.random.normal(
        jax.random.PRNGKey(11), (c_out, c_in // groups, k, k)
    ) * 0.2

    def fwd(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), ((padding, padding), (padding, padding)),
            rhs_dilation=(dilation, dilation), feature_group_count=groups,
            dimension_numbers=_DN,
        )

    y, vjp = jax.vjp(fwd, x, w)
    dy = jax.random.normal(jax.random.PRNGKey(12), y.shape)
    # one kept block per group where grouped (idx must be sorted and
    # spread block-diagonally); a ragged pair otherwise
    nb = c_out // bs
    blocks = (
        jnp.asarray([g * (nb // groups) for g in range(groups)], jnp.int32)
        if groups > 1
        else jnp.asarray([0, 2], jnp.int32)
    )
    chan = jnp.zeros((c_out,), bool)
    for b in np.asarray(blocks):
        chan = chan.at[b * bs : (b + 1) * bs].set(True)
    dy_masked = jnp.where(chan[None, :, None, None], dy, 0.0)
    dx_ref, dw_ref = vjp(dy_masked)
    common = dict(
        stride=(stride, stride), padding=((padding, padding), (padding, padding)),
        dilation=(dilation, dilation), groups=groups, block_size=bs,
    )
    return x, w, dy, blocks, dx_ref, dw_ref, common


@pytest.mark.parametrize("stride,padding,dilation,groups", _FUSED_GEOMS)
def test_conv_dx_fused_vs_vjp(stride, padding, dilation, groups):
    x, w, dy, blocks, dx_ref, _, common = _conv_fused_case(
        stride, padding, dilation, groups
    )
    dx = ops.conv_dx_fused(dy, w, blocks, hw=x.shape[2:], **common)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stride,padding,dilation,groups", _FUSED_GEOMS)
def test_conv_dw_fused_vs_vjp(stride, padding, dilation, groups):
    x, w, dy, blocks, _, dw_ref, common = _conv_fused_case(
        stride, padding, dilation, groups
    )
    kh, kw = w.shape[2:]
    dw2 = ops.conv_dw_fused_scatter(x, dy, blocks, kh=kh, kw=kw, **common)
    # [Cg*Kh*Kw, C_out] rows in (c, kh, kw) order -> OIHW
    expect = dw_ref.transpose(1, 2, 3, 0).reshape(-1, w.shape[0])
    np.testing.assert_allclose(dw2, expect, rtol=1e-4, atol=1e-4)


def test_conv_dw_fused_ragged_c_out():
    # c_out=10 with block_size=4: the phantom tail channels must stay
    # out of the scattered result
    x, w, dy, blocks, _, dw_ref, common = _conv_fused_case(
        1, 1, 1, 1, c_out=10, bs=4
    )
    dw2 = ops.conv_dw_fused_scatter(x, dy, blocks, kh=3, kw=3, **common)
    expect = dw_ref.transpose(1, 2, 3, 0).reshape(-1, 10)
    np.testing.assert_allclose(dw2, expect, rtol=1e-4, atol=1e-4)


# --- paged attention vs the gather + masked-attention oracle ---------


def _paged_attn_ref(q, k_pool, v_pool, tables, qpos):
    b, s, h, d = q.shape
    n_pages, bs_pg, kv, _ = k_pool.shape
    nb = tables.shape[1]
    tables = jnp.clip(tables, 0, n_pages - 1)
    g = h // kv
    kk = jnp.repeat(k_pool[tables].reshape(b, nb * bs_pg, kv, d), g, axis=2)
    vv = jnp.repeat(v_pool[tables].reshape(b, nb * bs_pg, kv, d), g, axis=2)
    t = jnp.arange(nb * bs_pg)
    mask = t[None, None, :] <= qpos[:, :, None]
    scores = jnp.einsum("bshd,bthd->bhst", q, kk).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhst,bthd->bshd", p, vv.astype(jnp.float32)
    ).astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv,s", [(4, 2, 2), (4, 4, 1)])
def test_paged_attention_vs_gather(dtype, h, kv, s):
    b, d, n_pages, bs_pg, nb = 3, 8, 10, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(20), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k_pool = jax.random.normal(ks[1], (n_pages, bs_pg, kv, d), dtype)
    v_pool = jax.random.normal(ks[2], (n_pages, bs_pg, kv, d), dtype)
    tables = jax.random.randint(ks[3], (b, nb), 0, n_pages)
    # heterogeneous positions: slots mid-page, page-boundary, deep
    qpos = jnp.stack([jnp.arange(s) + off for off in (1, 4, 7)]).astype(jnp.int32)
    out = ops.paged_attention(q, k_pool, v_pool, tables, qpos)
    expect = _paged_attn_ref(q, k_pool, v_pool, tables, qpos)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, jnp.float32), np.asarray(expect, jnp.float32),
        rtol=tol, atol=tol,
    )


def test_paged_attention_ignores_unassigned_pages():
    """Table entries past the causal horizon may be stale or garbage —
    the per-token fence (t_pos <= qpos) must keep them out, and
    out-of-range page ids must not fault (they are clipped, then
    masked)."""
    b, s, h, kv, d = 2, 1, 4, 2, 8
    n_pages, bs_pg, nb = 6, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k_pool = jax.random.normal(ks[1], (n_pages, bs_pg, kv, d))
    v_pool = jax.random.normal(ks[2], (n_pages, bs_pg, kv, d))
    qpos = jnp.asarray([[2], [5]], jnp.int32)  # pages 2+ never reached
    good = jnp.asarray([[0, 1, 2], [3, 4, 2]], jnp.int32)
    bad = good.at[:, 2].set(jnp.asarray([999, -7]))
    out_good = ops.paged_attention(q, k_pool, v_pool, good, qpos)
    out_bad = ops.paged_attention(q, k_pool, v_pool, bad, qpos)
    np.testing.assert_allclose(out_good, out_bad, rtol=1e-6, atol=1e-6)


def test_pallas_block_size_must_tile_lanes_on_tpu(monkeypatch):
    """Compiled kernels carry a channel block on the 128-wide lane axis:
    a smaller block is refused with a clear error before Mosaic sees it
    (interpret mode, used above at block size 32, takes any size)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    dy = jnp.ones((128, 64), jnp.float32)
    w = jnp.ones((128, 64), jnp.float32)
    idx = jnp.asarray([0], jnp.int32)
    try:
        with pytest.raises(ValueError, match="multiple of the 128-wide lane"):
            ops.dx_gathered(dy, w, idx, block_size=32)
        with pytest.raises(ValueError, match="multiple of the 128-wide lane"):
            ops.dw_gathered_scatter(dy, dy, idx, n_out=64, block_size=32)
    finally:
        jax.clear_caches()
