"""Conv/dense backward parity through the unified engine.

The engine's core guarantee (``repro.core.backward``): mask mode and
gather mode share one selection per call, so gather-mode gradients equal
the mask-mode oracle to accumulation tolerance — across geometry
(stride × padding × dilation × groups), granularity, ``bwd_dtype``, TP
sharding, and the Pallas block path (interpret mode on CPU). Plus the
ragged-tail regression the old per-op implementations failed:
``C % block_size != 0`` must not double-count or overwrite the last
channel.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse_conv2d, sparse_dense, sparsity
from repro.core.policy import SsPropPolicy, tpu_default


def _pol(granularity, bwd_dtype, *, mask=False, block_size=8, rate=0.5, **kw):
    return SsPropPolicy(
        rate,
        granularity=granularity,
        block_size=block_size,
        mask_mode=mask,
        bwd_dtype=bwd_dtype,
        **kw,
    )


def _tols(bwd_dtype):
    if bwd_dtype == "bfloat16":
        return dict(rtol=3e-2, atol=3e-2)
    return dict(rtol=2e-4, atol=1e-5)


def _conv_grads(pol, stride, padding, dilation, groups, c_out=16):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 8, 8))
    w = jax.random.normal(
        jax.random.PRNGKey(1), (c_out, 6 // groups, 3, 3)
    ) * 0.2
    b = jax.random.normal(jax.random.PRNGKey(2), (c_out,))

    def loss(x, w, b):
        y = sparse_conv2d(
            x, w, b,
            stride=stride, padding=padding, dilation=dilation, groups=groups,
            policy=pol,
        )
        return 0.5 * (y ** 2).mean()

    return jax.grad(loss, argnums=(0, 1, 2))(x, w, b)


def _dense_grads(pol, d_out=32):
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 24))
    w = jax.random.normal(jax.random.PRNGKey(4), (24, d_out)) * 0.2
    b = jax.random.normal(jax.random.PRNGKey(5), (d_out,))

    def loss(x, w, b):
        return 0.5 * (sparse_dense(x, w, b, policy=pol) ** 2).mean()

    return jax.grad(loss, argnums=(0, 1, 2))(x, w, b)


# geometry: full stride×padding cross, dilation/groups folded in
GEOMS = [
    # (stride, padding, dilation, groups)
    (1, 1, 1, 1),
    (2, 1, 1, 1),
    (1, 0, 1, 1),
    (2, 0, 1, 1),
    (1, 1, 2, 1),
    (2, 0, 2, 1),
    (1, 1, 1, 2),
    (2, 1, 2, 2),
]
CFGS = [
    ("channel", ""),
    ("block", ""),
    ("channel", "bfloat16"),
    ("block", "bfloat16"),
]


class TestConvParityGrid:
    @pytest.mark.parametrize("granularity,bwd_dtype", CFGS)
    @pytest.mark.parametrize("stride,padding,dilation,groups", GEOMS)
    def test_gather_equals_mask_oracle(
        self, stride, padding, dilation, groups, granularity, bwd_dtype
    ):
        g_gather = _conv_grads(
            _pol(granularity, bwd_dtype), stride, padding, dilation, groups
        )
        g_mask = _conv_grads(
            _pol(granularity, bwd_dtype, mask=True), stride, padding, dilation, groups
        )
        for name, a, r in zip(("dx", "dw", "db"), g_gather, g_mask, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), err_msg=name, **_tols(bwd_dtype)
            )

    @pytest.mark.parametrize("granularity,bwd_dtype", CFGS)
    def test_conv_tp_shards_gather_equals_mask(self, granularity, bwd_dtype):
        # 64 channels: each of 4 shards holds two 8-channel blocks and
        # keeps one (16 channels, one block, would keep every channel)
        g1 = _conv_grads(_pol(granularity, bwd_dtype, tp_shards=4), 1, 1, 1, 1, c_out=64)
        g2 = _conv_grads(
            _pol(granularity, bwd_dtype, mask=True, tp_shards=4), 1, 1, 1, 1, c_out=64
        )
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), err_msg=name, **_tols(bwd_dtype)
            )

    def test_conv_tp_shards_balanced(self):
        # 4 shards of 4 channels at rate 0.5 -> 2 kept per shard
        _, dw, _ = _conv_grads(_pol("channel", "", tp_shards=4), 1, 1, 1, 1)
        kept = (np.abs(np.asarray(dw)).sum((1, 2, 3)) != 0).reshape(4, 4).sum(1)
        assert (kept == kept[0]).all()


class TestDenseParityGrid:
    @pytest.mark.parametrize("granularity,bwd_dtype", CFGS)
    @pytest.mark.parametrize("tp_shards", [0, 4])
    def test_gather_equals_mask_oracle(self, granularity, bwd_dtype, tp_shards):
        # 64 outputs: with 4 shards each drops one of its two 8-channel
        # blocks (32 outputs, one block a shard, would keep every channel)
        g1 = _dense_grads(_pol(granularity, bwd_dtype, tp_shards=tp_shards), d_out=64)
        g2 = _dense_grads(
            _pol(granularity, bwd_dtype, mask=True, tp_shards=tp_shards), d_out=64
        )
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), err_msg=name, **_tols(bwd_dtype)
            )


class TestPallasParity:
    """The acceptance-criterion paths: block granularity through the
    Pallas gathered kernels, interpret mode on CPU, fp32 tolerance."""

    @pytest.mark.parametrize(
        "stride,padding,dilation", [(1, 1, 1), (2, 0, 1), (1, 1, 2)]
    )
    def test_conv_pallas_block_vs_mask(self, stride, padding, dilation):
        pol = _pol("block", "", block_size=8, use_pallas=True)
        ref = _pol("block", "", block_size=8, mask=True)
        g1 = _conv_grads(pol, stride, padding, dilation, 1)
        g2 = _conv_grads(ref, stride, padding, dilation, 1)
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4, err_msg=name
            )

    def test_conv_pallas_bf16(self):
        pol = _pol("block", "bfloat16", block_size=8, use_pallas=True)
        ref = _pol("block", "bfloat16", block_size=8, mask=True)
        g1 = _conv_grads(pol, 1, 1, 1, 1)
        g2 = _conv_grads(ref, 1, 1, 1, 1)
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), err_msg=name, **_tols("bfloat16")
            )

    def _spy(self, monkeypatch, names):
        from repro.kernels import ops as kops

        calls = dict.fromkeys(names, 0)
        for name in names:
            real = getattr(kops, name)

            def spy(*a, _name=name, _real=real, **kw):
                calls[_name] += 1
                return _real(*a, **kw)

            monkeypatch.setattr(kops, name, spy)
        return calls

    def test_conv_pallas_path_actually_routes_through_kernels(self, monkeypatch):
        # fuse_im2col is on by default: the fused kernels take the call,
        # the materializing canonical kernels are never touched.
        calls = self._spy(
            monkeypatch,
            ("conv_dx_fused", "conv_dw_fused_scatter",
             "dx_gathered", "dw_gathered_scatter"),
        )
        _conv_grads(_pol("block", "", block_size=8, use_pallas=True), 1, 1, 1, 1)
        assert calls["conv_dx_fused"] == 1 and calls["conv_dw_fused_scatter"] == 1
        assert calls["dx_gathered"] == 0 and calls["dw_gathered_scatter"] == 0

    def test_conv_pallas_fuse_off_routes_materializing(self, monkeypatch):
        calls = self._spy(
            monkeypatch,
            ("conv_dx_fused", "conv_dw_fused_scatter",
             "dx_gathered", "dw_gathered_scatter"),
        )
        _conv_grads(
            _pol("block", "", block_size=8, use_pallas=True, fuse_im2col=False),
            1, 1, 1, 1,
        )
        assert calls["dx_gathered"] == 1 and calls["dw_gathered_scatter"] == 1
        assert calls["conv_dx_fused"] == 0 and calls["conv_dw_fused_scatter"] == 0

    @pytest.mark.parametrize("stride,padding,dilation,groups", GEOMS)
    def test_conv_fused_equals_materialized(
        self, stride, padding, dilation, groups
    ):
        # the tentpole contract: the fused index-map kernels compute the
        # same backward as the materializing canonical path, across the
        # full geometry grid (selection is identical — only the lowering
        # differs).
        pol = _pol("block", "", block_size=4, use_pallas=True)
        ref = dataclasses.replace(pol, fuse_im2col=False)
        g1 = _conv_grads(pol, stride, padding, dilation, groups)
        g2 = _conv_grads(ref, stride, padding, dilation, groups)
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4, err_msg=name
            )

    def test_conv_pallas_grouped_routes_fused_block_diagonal(self, monkeypatch):
        # grouped convs route onto the SAME fused kernels via the
        # block-diagonal canonical form (whole blocks per group) — the
        # old framework-VJP fallback is only for indivisible shapes.
        calls = self._spy(monkeypatch, ("conv_dx_fused", "conv_dw_fused_scatter"))
        pol = _pol("block", "", block_size=4, use_pallas=True)
        ref = _pol("block", "", block_size=4, mask=True)
        g1 = _conv_grads(pol, 1, 1, 1, 2)
        g2 = _conv_grads(ref, 1, 1, 1, 2)
        assert calls["conv_dx_fused"] == 1 and calls["conv_dw_fused_scatter"] == 1
        for a, r in zip(g1, g2, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4)

    def test_conv_pallas_grouped_indivisible_falls_back(self, monkeypatch):
        # c_out=48 with groups=2 holds 24 channels a group; block_size=16
        # can't split block-diagonally -> framework VJP of the gathered
        # conv (each group keeps 8 of its 24), still exact.
        calls = self._spy(monkeypatch, ("conv_dx_fused", "conv_dw_fused_scatter"))
        pol = _pol("block", "", block_size=16, use_pallas=True)
        ref = _pol("block", "", block_size=16, mask=True)
        assert not sparsity.keeps_every_channel(48, pol, 2)
        g1 = _conv_grads(pol, 1, 1, 1, 2, c_out=48)
        g2 = _conv_grads(ref, 1, 1, 1, 2, c_out=48)
        assert calls["conv_dx_fused"] == 0 and calls["conv_dw_fused_scatter"] == 0
        for a, r in zip(g1, g2, strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4, atol=1e-5)


class TestAllKeptSites:
    """A site whose selection keeps every channel (one 128-channel block at
    0.8) contracts densely: no selection, no Pallas kernel, and the same
    gradients as the route that selects the identity and as the float32
    dense VJP."""

    POLICY = dataclasses.replace(tpu_default(0.8), use_pallas=True)
    # (c_out, groups, tp_shards, block_size): whole blocks per group or
    # shard where the selected route can reach the Pallas kernels
    SITES = [
        (64, 1, 0, 128),
        (128, 1, 0, 128),
        (128, 2, 0, 64),
        (64, 2, 0, 128),
        (128, 1, 4, 32),
    ]

    def _conv(self, c_out, groups, tp_shards, block_size):
        from repro.core import conv

        pol = dataclasses.replace(
            self.POLICY, tp_shards=tp_shards, block_size=block_size
        )
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        # large enough that the fused kernels move fewer bytes than the
        # patch buffers, so the selected route takes them
        x = jax.random.normal(ks[0], (2, 32, 16, 16))
        w = jax.random.normal(ks[1], (c_out, 32 // groups, 3, 3)) * 0.2
        dy = jax.random.normal(ks[2], (2, c_out, 16, 16))
        pads = ((1, 1), (1, 1))
        op = conv._ConvOp(x, w, (1, 1), pads, (1, 1), groups, pol)

        def ref(dy):
            with jax.default_matmul_precision("highest"):
                return conv._ConvOp(x, w, (1, 1), pads, (1, 1), groups, pol).contract_full(dy)

        return pol, op, dy, ref

    @staticmethod
    def _close(name, a, r):
        # float32 accumulation: a sum of n products errs by about
        # eps * sqrt(n) of the gradient's scale, not of each element
        a, r = np.asarray(a), np.asarray(r)
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=1e-6 * np.abs(r).max(), err_msg=name)

    def _selected_route(self, pol, op, dy):
        """The route the engine takes when it selects: here the identity."""
        from repro.core import backward

        sel = sparsity.select(dy, pol, channel_axis=1, n_shards=op.selection_shards(pol))
        return backward._contract_selected(pol, op, dy, sel, 1, True, True)

    @pytest.mark.parametrize("bias", [False, True])
    @pytest.mark.parametrize("c_out,groups,tp_shards,block_size", SITES)
    def test_conv_equals_selected_route_and_dense_vjp(
        self, c_out, groups, tp_shards, block_size, bias
    ):
        from repro.core import backward

        pol, op, dy, ref = self._conv(c_out, groups, tp_shards, block_size)
        assert sparsity.keeps_every_channel(c_out, pol, op.selection_shards(pol))
        dx, dw, db = backward.channel_sparse_backward(pol, op, dy, has_bias=bias)
        sx, sw = self._selected_route(pol, op, dy)
        rx, rw = ref(dy)
        for name, a, b, r in (("dx", dx, sx, rx), ("dw", dw, sw, rw)):
            self._close(name, a, b)
            self._close(name, a, r)
        if bias:
            self._close("db", db, dy.sum((0, 2, 3)))
        else:
            assert db is None

    @pytest.mark.parametrize("d_out,tp_shards", [(64, 0), (128, 0), (128, 4)])
    def test_dense_equals_selected_route_and_dense_vjp(self, d_out, tp_shards):
        from repro.core import backward, dense

        pol = dataclasses.replace(self.POLICY, tp_shards=tp_shards)
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        x = jax.random.normal(ks[0], (32, 48))
        w = jax.random.normal(ks[1], (48, d_out)) * 0.2
        dy = jax.random.normal(ks[2], (32, d_out))
        op = dense._DenseOp(x, w, pol)
        assert sparsity.keeps_every_channel(d_out, pol, op.selection_shards(pol))
        dx, dw, db = backward.channel_sparse_backward(pol, op, dy, has_bias=True)
        sx, sw = self._selected_route(pol, op, dy)
        with jax.default_matmul_precision("highest"):
            rx, rw = dy @ w.T, x.T @ dy
        for name, a, b, r in (("dx", dx, sx, rx), ("dw", dw, sw, rw)):
            self._close(name, a, b)
            self._close(name, a, r)
        self._close("db", db, dy.sum(0))

    @pytest.mark.parametrize(
        "c_out,groups,tp_shards,block_size", [s for s in SITES if s != (64, 2, 0, 128)]
    )
    def test_selected_route_reaches_the_fused_kernels(
        self, monkeypatch, c_out, groups, tp_shards, block_size
    ):
        # the comparison above is against the fused Pallas route wherever
        # whole blocks allow it (64 channels in two groups of 32 cannot
        # hold a 128-channel block, so that case selects through XLA)
        from repro.kernels import ops as kops

        calls = []
        real = kops.conv_dx_fused
        monkeypatch.setattr(
            kops, "conv_dx_fused", lambda *a, **kw: calls.append(1) or real(*a, **kw)
        )
        pol, op, dy, _ = self._conv(c_out, groups, tp_shards, block_size)
        self._selected_route(pol, op, dy)
        assert calls

    @pytest.mark.parametrize(
        "c_out,mask,kept_all",
        [(64, False, True), (128, False, True), (128, True, True),
         (256, False, False), (256, True, False)],
    )
    def test_route_in_jaxpr(self, c_out, mask, kept_all):
        # an engaged site holds no kernel and no top-k; a site that drops
        # a block (256 channels = 2 blocks, 1 kept) still selects and, off
        # mask mode, runs the fused kernel
        pol = dataclasses.replace(self.POLICY, mask_mode=mask)
        x = jnp.zeros((2, 64, 16, 16))
        w = jnp.zeros((c_out, 64, 3, 3))

        def loss(x, w):
            return jnp.sum(sparse_conv2d(x, w, padding=1, policy=pol) ** 2)

        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w))
        assert ("top_k" in text) == (not kept_all)
        if kept_all or mask:
            assert "pallas_call" not in text
        else:
            assert "conv_dx_fused" in text and "pallas_call" in text


class TestRaggedTailRegression:
    """C=130 with block_size=128: the tail block's clamped phantom
    indices used to double-count dX and overwrite dW/db of channel 129."""

    def _dense(self, pol):
        return _dense_grads(pol, d_out=130)

    def _make_tail_kept_policy(self, **kw):
        # rate 0.5 over 2 blocks keeps exactly 1; seeds below make the
        # tail block win often enough that both cases are exercised by
        # the pair of d_out values.
        return dataclasses.replace(tpu_default(0.5), block_size=128, **kw)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_dense_c130_gather_equals_mask(self, use_pallas):
        pol = self._make_tail_kept_policy(use_pallas=use_pallas)
        ref = self._make_tail_kept_policy(mask_mode=True)
        g1 = self._dense(pol)
        g2 = self._dense(ref)
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4, err_msg=name
            )

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_conv_c130_gather_equals_mask(self, use_pallas):
        pol = self._make_tail_kept_policy(use_pallas=use_pallas)
        ref = self._make_tail_kept_policy(mask_mode=True)
        g1 = _conv_grads(pol, 1, 1, 1, 1, c_out=130)
        g2 = _conv_grads(ref, 1, 1, 1, 1, c_out=130)
        for name, a, r in zip(("dx", "dw", "db"), g1, g2, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4, err_msg=name
            )

    def test_select_marks_phantom_slots(self):
        # force the tail block to win: channels 128..129 carry the mass
        dy = jnp.zeros((4, 130)).at[:, 128:].set(10.0)
        pol = dataclasses.replace(tpu_default(0.5), block_size=128)
        sel = sparsity.select(dy, pol, channel_axis=-1)
        assert sel.k == 128
        assert sel.valid is not None
        assert int(np.asarray(sel.valid).sum()) == 2  # only 128, 129 real
        assert int(np.asarray(sel.idx).max()) == 129  # clamped in range
        # block 1 was selected
        assert np.asarray(sel.block_idx).tolist() == [1]

    def test_scatter_add_ignores_phantom_duplicates(self):
        # 3 slots all pointing at channel 1, only slot 0 valid
        from repro.core import backward

        compact = jnp.array([[1.0, 0.0, 0.0]])
        idx = jnp.array([1, 1, 1])
        out = backward.scatter_channels(compact, idx, 4, axis=1)
        np.testing.assert_array_equal(
            np.asarray(out), np.array([[0.0, 1.0, 0.0, 0.0]])
        )


class TestSparsifyFlags:
    """``sparsify_dx`` / ``sparsify_dw`` select WHICH gradient shrinks.

    The un-sparsified side must reproduce the dense gradient *exactly*
    (same full-size contraction, not an approximation), in both gather
    and mask mode, for dense and conv ops.
    """

    DENSE = SsPropPolicy(0.0)

    @pytest.mark.parametrize("granularity", ["channel", "block"])
    @pytest.mark.parametrize("mask", [False, True])
    def test_dense_dx_off_is_exactly_dense(self, granularity, mask):
        pol = _pol(granularity, "", mask=mask, sparsify_dx=False)
        dx, dw, _ = _dense_grads(pol)
        dx_ref, dw_ref, _ = _dense_grads(self.DENSE)
        np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))
        # dw still sparsified: dropped channels are exact zeros
        assert (np.asarray(dw) == 0).all(0).sum() > (np.asarray(dw_ref) == 0).all(0).sum()

    @pytest.mark.parametrize("granularity", ["channel", "block"])
    @pytest.mark.parametrize("mask", [False, True])
    def test_dense_dw_off_is_exactly_dense(self, granularity, mask):
        pol = _pol(granularity, "", mask=mask, sparsify_dw=False)
        _, dw, db = _dense_grads(pol)
        _, dw_ref, db_ref = _dense_grads(self.DENSE)
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_ref))
        np.testing.assert_array_equal(np.asarray(db), np.asarray(db_ref))

    @pytest.mark.parametrize("mask", [False, True])
    def test_conv_dx_off_is_exactly_dense(self, mask):
        pol = _pol("channel", "", mask=mask, sparsify_dx=False)
        dx, dw, _ = _conv_grads(pol, 1, 1, 1, 1)
        dx_ref, dw_ref, _ = _conv_grads(self.DENSE, 1, 1, 1, 1)
        np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))
        assert (np.abs(np.asarray(dw)).sum((1, 2, 3)) == 0).sum() > 0

    def test_both_off_is_dense_path(self):
        pol = _pol("channel", "", sparsify_dx=False, sparsify_dw=False)
        for a, r in zip(_dense_grads(pol), _dense_grads(self.DENSE), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r))

    def test_pallas_block_respects_flags(self):
        pol = _pol("block", "", sparsify_dx=False, use_pallas=True)
        dx, dw, _ = _dense_grads(pol)
        dx_ref, _, _ = _dense_grads(self.DENSE)
        np.testing.assert_allclose(
            np.asarray(dx), np.asarray(dx_ref), rtol=1e-5, atol=1e-6
        )
        assert (np.asarray(dw) == 0).all(0).sum() > 0

    def test_flops_flags_monotone(self):
        from repro.core import flops

        base = SsPropPolicy(0.8)
        both = flops.dense_backward_flops_policy(128, 256, 512, base)
        dx_only = flops.dense_backward_flops_policy(
            128, 256, 512, dataclasses.replace(base, sparsify_dw=False)
        )
        off = flops.dense_backward_flops_policy(
            128, 256, 512, dataclasses.replace(base, sparsify_dx=False, sparsify_dw=False)
        )
        dense = flops.dense_backward_flops(128, 256, 512)
        assert both < dx_only < off == dense
        cb = flops.conv_backward_flops_policy(8, 16, 16, 64, 128, 3, base)
        cd = flops.conv_backward_flops_policy(
            8, 16, 16, 64, 128, 3, dataclasses.replace(base, sparsify_dx=False)
        )
        assert cb < cd < flops.conv_backward_flops(8, 16, 16, 64, 128, 3)
