"""The program names its layers: every operation of a conv or dense site
carries the site's name scope in its HLO ``op_name``, forward and
backward; the sparse backward's selection and contraction carry
``ssprop_select`` and ``ssprop_contract`` on every route, and a site whose
selection would keep every channel contracts under
``ssprop_contract/ssprop_all_kept`` and selects nothing; the generic
gathered route, which every conv site that drops a block takes, contracts
under ``ssprop_contract/ssprop_gathered``; the TP-local route contracts
under ``ssprop_contract/ssprop_tp``; attention's own work
carries ``attn_core`` and the LM loss head ``lm_loss``; the optimizer's
update carries ``adam``. Scopes are metadata: they change no instruction.
"""
from __future__ import annotations

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.policy import SsPropPolicy, tpu_default
from repro.launch import steps
from repro.models import layers, resnet
from repro.optim import adam

SITE = "block_4/conv1"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC = re.compile(r'loc\("([^"]*)"')
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*? ([a-z][a-z0-9_-]*)\(", re.M)

BLOCK = tpu_default(0.5)  # 256 channels: 1 of 2 128-channel blocks kept
GATHERED = "ssprop_contract/ssprop_gathered/transpose(jvp())"
ROUTES = {
    # route: (policy, op_name fragment only that route emits)
    "dense": (SsPropPolicy(), "ssprop_contract/transpose"),
    "mask": (dataclasses.replace(BLOCK, mask_mode=True), "ssprop_contract/mul"),
    "gathered": (SsPropPolicy(drop_rate=0.5), GATHERED),
    # the policies that reached the Pallas conv kernels (the canonical
    # route also ran dX alone beside a dense dW): a conv now takes XLA's
    # gathered conv under both
    "canonical": (dataclasses.replace(BLOCK, use_pallas=True, sparsify_dw=False), GATHERED),
    "fused": (dataclasses.replace(BLOCK, use_pallas=True), GATHERED),
    # one 256-channel block, so selection would keep every channel
    "all_kept": (
        dataclasses.replace(BLOCK, use_pallas=True, block_size=256),
        "ssprop_contract/ssprop_all_kept/transpose",
    ),
}


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _conv_grad_hlo(policy, site=SITE) -> str:
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (2, 64, 16, 16), jnp.float32)
    p = layers.conv2d_init(kw, 256, 64, 3)

    def loss(p, x):
        y = layers.conv_apply(p, x, policy, padding=1, site=site)
        return jnp.sum(y * y)

    return _hlo(jax.grad(loss, argnums=(0, 1)), p, x)


@pytest.mark.parametrize("route", list(ROUTES))
def test_conv_site_and_stages_name_every_route(route):
    policy, marker = ROUTES[route]
    names = set(_OP_NAME.findall(_conv_grad_hlo(policy)))
    fwd, bwd = f"jvp({SITE})", f"transpose(jvp({SITE}))"
    assert any(fwd in n and bwd not in n for n in names)
    assert any(f"{bwd}/{marker}" in n for n in names), route
    selected = any(f"{bwd}/ssprop_select/" in n for n in names)
    assert selected == (route not in ("dense", "all_kept"))
    assert not any("pallas" in n or "_fused" in n or "dx_gathered" in n for n in names)


@pytest.fixture(scope="module")
def resnet18_step():
    """The ResNet-18 training step at drop rate 0.8 with ``use_pallas``
    on, as the benchmark's sparse cells build it: its jaxpr and the
    scope names of its lowering."""
    policy = dataclasses.replace(tpu_default(0.8), use_pallas=True)
    params = jax.eval_shape(
        lambda r: resnet.init_params("resnet18", r, 10), jax.random.key(0)
    )
    step = steps.make_classifier_step("resnet18", policy, adam.AdamConfig())
    args = (
        params, jax.eval_shape(adam.init, params),
        jax.ShapeDtypeStruct((2, 3, 32, 32), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    names = set(_LOC.findall(jax.jit(step).lower(*args).as_text(debug_info=True)))
    return jax.make_jaxpr(step)(*args), names


def _resnet18_sites():
    sites, _ = resnet.site_names("resnet18")
    widths = {s: c_out for s, _, c_out, *_ in resnet.iter_conv_shapes("resnet18", (3, 32, 32))}
    return sites, widths


def _scoped(names, site, stage):
    return any(f"transpose(jvp({site}))/{stage}/" in n for n in names)


def test_resnet18_scopes_the_sites_that_keep_every_channel(resnet18_step):
    """At 0.8 in 128-channel blocks, the ten ResNet-18 sites of 64 and 128
    channels keep their only block: their backward runs under
    ``ssprop_all_kept`` and selects nothing; the ten of 256 and 512
    channels select and never enter that scope."""
    _, names = resnet18_step
    sites, widths = _resnet18_sites()
    kept_all = [s for s in sites if _scoped(names, s, "ssprop_contract/ssprop_all_kept")]
    selecting = [s for s in sites if _scoped(names, s, "ssprop_select")]
    assert kept_all == [s for s in sites if widths[s] <= 128]
    assert len(kept_all) == 10
    assert selecting == [s for s in sites if s not in kept_all]


def test_resnet18_step_holds_no_kernel_and_gathers_the_sites_that_drop_a_block(
    resnet18_step,
):
    """With ``use_pallas`` on, the ResNet-18 step at 0.8 runs no Pallas
    kernel: the ten sites of 256 and 512 channels, which drop a block,
    contract through XLA's gathered conv under ``ssprop_gathered``, and
    no other site does."""
    jaxpr, names = resnet18_step
    assert "pallas_call" not in str(jaxpr)
    sites, widths = _resnet18_sites()
    gathered = [s for s in sites if _scoped(names, s, "ssprop_contract/ssprop_gathered")]
    assert gathered == [s for s in sites if widths[s] > 128]
    assert gathered == [
        f"block_{b}/{c}" for b in (4, 5, 6, 7) for c in ("conv1", "conv2", "down")
        if c != "down" or b in (4, 6)
    ]


def test_dense_site_is_scoped():
    kx, kw = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (4, 16), jnp.float32)
    p = layers.dense_init(kw, 16, 256, dtype=jnp.float32)
    site = "layer_3/attn/q"

    def loss(p, x):
        return jnp.sum(layers.dense_apply(p, x, BLOCK, site=site) ** 2)

    names = set(_OP_NAME.findall(_hlo(jax.grad(loss), p, x)))
    assert any(f"transpose(jvp({site}))/ssprop_select/" in n for n in names)
    assert any(f"transpose(jvp({site}))/ssprop_contract/" in n for n in names)


def test_unnamed_call_adds_no_scope():
    names = _OP_NAME.findall(_conv_grad_hlo(SsPropPolicy(), site=""))
    assert not any(SITE in n or "jvp(/" in n or "jvp()/ssprop" in n for n in names)
    assert any("ssprop_contract" in n for n in names)


def test_adam_update_is_scoped():
    params = {"w": jnp.ones((8, 8)), "b": jnp.zeros((8,))}
    grads = jax.tree.map(jnp.ones_like, params)

    def step(p, g, s):
        return adam.apply_updates(adam.AdamConfig(), p, g, s)[:2]

    names = _OP_NAME.findall(_hlo(step, params, grads, adam.init(params)))
    assert names and all(n.split("/")[1] == "adam" for n in names if n.startswith("jit(step)/"))


def test_scopes_change_no_instruction():
    """The same conv step with and without its site scope compiles to the
    same instructions, opcode by opcode."""
    policy = dataclasses.replace(BLOCK, use_pallas=True)

    def count(site):
        return collections.Counter(_OPCODE.findall(_conv_grad_hlo(policy, site=site)))

    assert count(SITE) == count("")


def test_lm_step_scopes_attention_loss_and_tp_route():
    """The decoder's training step names attention's own work
    (``attn_core``), the loss head (``lm_loss``) and, at sites selecting
    per shard, the TP-local contraction (``ssprop_contract/ssprop_tp``)."""
    from repro.configs.registry import get_config
    from repro.core.policy import PolicyProgram
    from repro.models import model as lm

    cfg = get_config("qwen2.5-3b").reduced(d_ff=1024)
    sites, depth = lm.site_names(cfg)
    table = PolicyProgram.single(tpu_default(0.8)).resolve(sites, depth=depth)
    table = table.with_tp_shards(lambda s: 4 if s.endswith("mlp/up") else 1).peak()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    step = steps.make_train_step(cfg, table, adam.AdamConfig())
    names = _OP_NAME.findall(
        _hlo(step, params, adam.init(params), {"tokens": tokens, "targets": tokens})
    )
    parts = [set(re.split(r"[/()]", n)) for n in names]
    assert any("attn_core" in p for p in parts)
    assert any("lm_loss" in p for p in parts)
    tp = [n for n in names if "ssprop_contract/ssprop_tp/" in n]
    assert tp and all("mlp/up" in n for n in tp)
