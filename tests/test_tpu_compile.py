"""Compile the main path's kernels for a described TPU v5e, at real widths.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached (``jax.experimental.topologies``), so Mosaic refuses here
what it would refuse on the chip — unaligned blocks, strided slices it
cannot lower, operand layouts it cannot tile. Interpret-mode tests
(``test_kernels.py``) cannot see any of that.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.policy import tpu_default
from repro.kernels import ops
from repro.launch import steps
from repro.models import resnet
from repro.optim import adam

POLICY = tpu_default(0.8)

# ResNet-18 on CIFAR (batch 128): (c_in, c_out, h_in, k, stride, pad)
RESNET18_CONVS = [
    (64, 64, 32, 3, 1, 1),    # stage 1
    (64, 128, 32, 3, 2, 1),   # stage 2 entry
    (64, 128, 32, 1, 2, 0),   # stage 2 downsample
    (128, 256, 16, 3, 2, 1),  # stage 3 entry
    (256, 512, 8, 3, 2, 1),   # stage 4 entry
    (512, 512, 4, 3, 1, 1),   # stage 4
]
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip's sharding, with the persistent compilation cache
    off (entries compiled for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernel wrappers off interpret mode, which they choose
    from the default backend (the CPU here)."""
    jax.clear_caches()  # no jaxpr traced in interpret mode may be reused
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    jax.clear_caches()


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert CUSTOM_CALL in hlo
    return hlo


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("geom", RESNET18_CONVS)
def test_conv_fused_kernels_compile(one_chip, compiled_kernels, geom, dtype):
    c_in, c_out, h, k, stride, pad = geom
    b = 128
    h_out = (h + 2 * pad - k) // stride + 1
    kb = POLICY.keep_count(c_out)
    common = dict(
        stride=(stride, stride), padding=((pad, pad), (pad, pad)),
        dilation=(1, 1), groups=1, block_size=POLICY.block_size,
    )
    x = _spec(one_chip, (b, c_in, h, h), dtype)
    dy = _spec(one_chip, (b, c_out, h_out, h_out), dtype)
    w = _spec(one_chip, (c_out, c_in, k, k), dtype)
    idx = _spec(one_chip, (kb,), jnp.int32)
    _compile(
        functools.partial(ops.conv_dw_fused_scatter, kh=k, kw=k, **common),
        x, dy, idx,
    )
    _compile(functools.partial(ops.conv_dx_fused, hw=(h, h), **common), dy, w, idx)


def test_gathered_matmuls_compile_at_lm_mlp_width(one_chip, compiled_kernels):
    """qwen2.5-3b MLP up-projection (2048 -> 11008), 4096 tokens, bf16."""
    m, d, f = 4096, 2048, 11008
    kb = POLICY.keep_count(f)
    dy = _spec(one_chip, (m, f), jnp.bfloat16)
    idx = _spec(one_chip, (kb,), jnp.int32)
    _compile(ops.dx_gathered, dy, _spec(one_chip, (d, f), jnp.bfloat16), idx)
    _compile(
        functools.partial(ops.dw_gathered_scatter, n_out=f),
        _spec(one_chip, (m, d), jnp.bfloat16), dy, idx,
    )


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("s", [1, 8])
def test_paged_attention_compiles_at_qwen_heads(
    one_chip, compiled_kernels, s, precision
):
    """16 query / 2 KV heads of width 128, as the engine calls it: bf16
    queries against an fp32 page pool; S=1 decode, S=8 prefill chunk.
    Mosaic takes a contract precision for fp32 pairs only, so the kernel
    must also compile under ``jax.default_matmul_precision("highest")``."""
    b, nb, bs, n_pages = 4, 5, 16, 20
    pool = _spec(one_chip, (n_pages, bs, 2, 128))
    with jax.default_matmul_precision(precision):
        _compile(
            ops.paged_attention,
            _spec(one_chip, (b, s, 16, 128), jnp.bfloat16), pool, pool,
            _spec(one_chip, (b, nb), jnp.int32), _spec(one_chip, (b, s), jnp.int32),
        )


def test_resnet18_ssprop_step_compiles(one_chip, compiled_kernels):
    """The paper's CIFAR-10 step (batch 128) at drop rate 0.8 with the
    Pallas route and the default im2col fusion."""
    policy = dataclasses.replace(POLICY, use_pallas=True)
    a_params = jax.eval_shape(
        lambda r: resnet.init_params("resnet18", r, 10), jax.random.PRNGKey(0)
    )
    a_opt = jax.eval_shape(adam.init, a_params)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t
    )
    hlo = _compile(
        steps.make_classifier_step("resnet18", policy, adam.AdamConfig()),
        place(a_params), place(a_opt),
        _spec(one_chip, (128, 3, 32, 32)), _spec(one_chip, (128,), jnp.int32),
    )
    assert hlo.count(CUSTOM_CALL) >= len(RESNET18_CONVS)
