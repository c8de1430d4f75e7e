"""The LM training step against the plain float32 reference, on the CPU.

A reduced qwen2.5 (2 layers, GQA 4/2 with q/k/v bias, tied head, SwiGLU
of width 1024) takes one ``make_train_step`` step from seeded weights,
and so does the plain reference (``repro.models.reference``) from the
same weights and batch. The loss, every leaf's Adam first moment (a
tenth of the clipped gradient) and every leaf's update agree: at drop
rate 0, at 0.8 over 128-channel blocks selected globally, and at 0.8
with ``tp_shards=4`` on one device, where each shard keeps the blocks
the reference's per-shard choice keeps. ``launch.train.build`` gives
column-parallel sites the mesh's model degree and row-parallel ones none.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.policy import PolicyProgram, tpu_default
from repro.dist import sharding as shd
from repro.launch import steps, train
from repro.models import model as lm
from repro.models import reference as ref
from repro.optim import adam

SEED = 7
CFG = get_config("qwen2.5-3b").reduced(d_ff=1024)  # float32, no remat
OPT = adam.AdamConfig(lr=2e-4, clip_norm=1.0)
B, S = 2, 32
COLUMN = ("q", "k", "v", "gate", "up")


def _batch():
    toks = jax.random.randint(jax.random.key(SEED), (B, S + 1), 0, CFG.vocab, jnp.int32)
    return np.asarray(toks[:, :-1]), np.asarray(toks[:, 1:])


def _names(tree) -> dict[str, np.ndarray]:
    """``{leaf name: array}`` of a :func:`ref.layer_params` tree
    (``layer_1/attn/q/w``)."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]:
        parts = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if parts[0] == "layers":
            parts = [f"layer_{parts[1]}", *parts[2:]]
        out["/".join(parts)] = np.asarray(v, np.float32)
    return out


def _specs(rate: float, shards: int) -> dict:
    c = {"q": CFG.n_heads * CFG.head_dim, "k": CFG.n_kv_heads * CFG.head_dim,
         "v": CFG.n_kv_heads * CFG.head_dim, "o": CFG.d_model, "gate": CFG.d_ff,
         "up": CFG.d_ff, "down": CFG.d_model}
    return {n: ref.block_spec(c[n], rate, 128, shards if n in COLUMN else 1) for n in c}


def _program(rate: float, shards: int):
    sites, depth = lm.site_names(CFG)
    table = PolicyProgram.single(tpu_default(rate)).resolve(sites, depth=depth)
    mesh = types.SimpleNamespace(shape={"data": 1, "model": shards})
    table = table.with_tp_shards(lambda s: shd.output_shards(mesh, s)).peak()
    params = lm.init_params(CFG, jax.random.PRNGKey(SEED))
    tokens, targets = _batch()
    step = jax.jit(steps.make_train_step(CFG, table, OPT))
    new, opt, metrics = step(params, adam.init(params), {"tokens": tokens, "targets": targets})
    update = jax.tree.map(jnp.subtract, new, params)
    m, upd = (_names(ref.layer_params(t)) for t in (opt.m, update))
    return float(metrics["loss"]), m, upd, params


def _close(a: dict, b: dict, rtol: float):
    assert set(a) == set(b)
    for k in b:
        err = np.linalg.norm(a[k] - b[k])
        assert err <= rtol * max(np.linalg.norm(b[k]), 1e-30), (k, err, np.linalg.norm(b[k]))


def _kept_blocks(g: np.ndarray, spec) -> np.ndarray:
    """``[shards, blocks]`` 0/1 of a kernel's nonzero gradient blocks."""
    n, bs, _ = spec
    cols = np.sum(np.square(g), axis=0)
    cols = np.pad(cols, (0, -cols.size % bs))
    return (cols.reshape(n, -1, bs).sum(-1) > 0).astype(np.float32)


@pytest.mark.parametrize(
    "rate, shards", [(0.0, 1), (0.8, 1), (0.8, 4)], ids=["dense", "global", "tp4"]
)
def test_train_step_matches_reference(rate, shards):
    loss, m, upd, params = _program(rate, shards)
    specs = _specs(rate, shards)
    p0 = ref.layer_params(params)
    r_loss, _, r_m, r_new = ref.train_step(CFG, specs, OPT, p0, *_batch())
    r_m, r_upd = _names(r_m), _names(jax.tree.map(jnp.subtract, r_new, p0))
    assert loss == pytest.approx(float(r_loss), rel=1e-5)
    _close(m, r_m, 2e-5)  # a tenth of the clipped gradient, every leaf
    # Adam's first update is about lr * sign(g): an element whose gradient
    # is near zero may take either sign from float32 rounding
    _close(upd, r_upd, 1e-2)
    if rate > 0:
        for i in range(CFG.n_layers):
            for name, role in ref.PROJECTIONS.items():
                key = f"layer_{i}/{role}/{name}/w"
                got = _kept_blocks(m[key], specs[name])
                np.testing.assert_array_equal(got, _kept_blocks(r_m[key], specs[name]), err_msg=key)
                n, _, keep = specs[name]
                assert got.sum(axis=1).tolist() == [min(keep, got.shape[1])] * n, key


def test_tp4_selects_per_shard():
    """At tp_shards=4 the MLP's 1024 channels split 256 a shard, two
    128-channel blocks each, of which each shard keeps one; globally two
    of eight are kept; k's 64 channels split 16 a shard, one block of 16
    each, kept whole."""
    assert _specs(0.8, 4)["up"] == (4, 128, 1)
    assert _specs(0.8, 1)["up"] == (1, 128, 2)
    assert _specs(0.8, 4)["o"] == (1, 128, 1)
    assert _specs(0.8, 4)["k"] == (4, 16, 1)


def test_build_sets_tp_shards_from_the_mesh(monkeypatch):
    mesh = types.SimpleNamespace(shape={"data": 1, "model": 4})
    monkeypatch.setattr(train, "make_host_mesh", lambda data, model: mesh)
    args = train.build_parser().parse_args(
        ["--arch", "qwen2.5-3b", "--reduced", "--granularity", "block", "--scheduler", "constant"]
    )
    t = train.build(args)
    assert t.mesh is mesh
    shards = {n.split("/", 1)[1]: p.tp_shards for n, p in t.resolved.policies_for_step(0).entries}
    assert shards == {
        "attn/q": 4, "attn/k": 4, "attn/v": 4, "mlp/up": 4, "mlp/gate": 4,
        "attn/o": 0, "mlp/down": 0,
    }
    # the rule names the kernels that param_specs splits on their outputs
    specs = shd.param_specs(steps.abstract_state(t.cfg)[0])
    slot = specs["stack"]["slots"][0]
    for site, n in shards.items():
        role, proj = site.split("/")
        assert (slot[role][proj]["w"][-1] == "model") == (n == 4), site
