"""End-to-end behaviour tests: training convergence with ssProp, the
paper's headline claims on synthetic data, checkpoint/restart, elastic
resharding, serving, and distributed lowering on a local mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.registry import get_config
from repro.core.policy import SsPropPolicy, paper_default
from repro.core.schedulers import drop_rate_for_step
from repro.data.pipeline import (
    ImagePipeline,
    ImagePipelineConfig,
    TokenPipeline,
    TokenPipelineConfig,
)
from repro.dist import sharding as shd
from repro.dist.fault import HeartbeatMonitor, Heartbeat, StragglerTracker
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models import model as lm, resnet
from repro.optim import adam


def _train_resnet(policy_fn, steps=30, seed=0, name="resnet18", lr=1e-3):
    """Tiny ResNet on the synthetic image task; returns loss history."""
    pipe = ImagePipeline(ImagePipelineConfig((3, 16, 16), 10, 32, seed=1), n_train=256)
    params = resnet.init_params(name, jax.random.PRNGKey(seed), num_classes=10)
    opt_state = adam.init(params)
    opt_cfg = adam.AdamConfig(lr=lr)

    step_dense = jax.jit(steps_lib.make_classifier_step(name, SsPropPolicy(0.0), opt_cfg))
    step_sparse = jax.jit(steps_lib.make_classifier_step(name, paper_default(0.8), opt_cfg))

    hist = []
    for i in range(steps):
        batch = jax.tree.map(jnp.asarray, pipe.batch_at(i))
        rate = policy_fn(i)
        fn = step_sparse if rate > 0 else step_dense
        params, opt_state, lv = fn(params, opt_state, batch["images"], batch["labels"])
        hist.append(float(lv))
    return hist


class TestPaperClaims:
    def test_ssprop_trains_comparably_to_dense(self):
        """Headline claim: ~40% backward FLOPs saved with comparable loss."""
        dense = _train_resnet(lambda i: 0.0, steps=30)
        bar = _train_resnet(
            lambda i: drop_rate_for_step(
                "epoch_bar", step=i, steps_per_epoch=5, total_steps=30, target=0.8
            ),
            steps=30,
        )
        assert dense[-1] < dense[0] * 0.8  # training works at all
        assert bar[-1] < bar[0] * 0.85  # sparse training converges too
        # comparable: within 50% relative on this tiny task
        assert bar[-1] < dense[-1] * 1.5 + 0.3

    def test_lm_ssprop_trains(self):
        cfg = get_config("qwen2.5-3b").reduced()
        pipe = TokenPipeline(TokenPipelineConfig(cfg.vocab, 32, 8, seed=0))
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = adam.init(params)
        step = jax.jit(
            steps_lib.make_train_step(
                cfg, paper_default(0.8), adam.AdamConfig(lr=1e-3)
            )
        )
        hist = []
        for i in range(20):
            batch = jax.tree.map(jnp.asarray, pipe.batch_at(i))
            params, opt_state, m = step(params, opt_state, batch)
            hist.append(float(m["loss"]))
        assert hist[-1] < hist[0]
        assert np.isfinite(hist).all()


class TestCheckpointRestart:
    def test_roundtrip_preserves_training_state(self, tmp_path):
        d = str(tmp_path)
        params = {"w": jnp.arange(12.0).reshape(3, 4)}
        st = adam.init(params)
        ckpt_lib.save(d, 5, {"params": params, "m": st.m, "v": st.v})
        like = {"params": params, "m": st.m, "v": st.v}
        r = ckpt_lib.restore(d, 5, like)
        np.testing.assert_array_equal(r["params"]["w"], params["w"])

    def test_commit_marker_hides_partial(self, tmp_path):
        d = str(tmp_path)
        os.makedirs(os.path.join(d, "step_00000007"))
        assert ckpt_lib.list_steps(d) == []
        ckpt_lib.save(d, 9, {"x": jnp.ones(3)})
        assert ckpt_lib.list_steps(d) == [9]

    def test_gc_keeps_latest(self, tmp_path):
        d = str(tmp_path)
        for s in (1, 2, 3, 4, 5):
            ckpt_lib.save(d, s, {"x": jnp.ones(2)}, keep=2)
        assert ckpt_lib.list_steps(d) == [4, 5]

    def test_elastic_reshard_across_meshes(self, tmp_path):
        """Save, then restore under an explicit (different) sharding."""
        d = str(tmp_path)
        w = jnp.arange(64.0).reshape(8, 8)
        ckpt_lib.save(d, 1, {"w": w})
        mesh = make_host_mesh(1, 1)
        sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(None, None))
        r = ckpt_lib.restore(d, 1, {"w": w}, shardings={"w": sh})
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(w))

    def test_train_cli_crash_resume(self, tmp_path):
        """Full driver: injected crash, auto-restart, bit-exact replay."""
        from repro.launch.train import build_parser, run

        args = build_parser().parse_args(
            [
                "--arch", "qwen2.5-3b", "--reduced", "--steps", "12",
                "--steps-per-epoch", "4", "--ckpt-dir", str(tmp_path),
                "--ckpt-every", "4", "--fail-at-step", "6",
                "--global-batch", "4", "--seq-len", "32", "--log-every", "100",
            ]
        )
        out = run(args)
        assert out["final_loss"] is not None and np.isfinite(out["final_loss"])
        assert ckpt_lib.latest_step(str(tmp_path)) == 12


class TestFaultTolerance:
    def test_heartbeat_monitor(self, tmp_path):
        d = str(tmp_path)
        hb = Heartbeat(d, rank=3, interval_s=0.0)
        hb.beat(force=True)
        mon = HeartbeatMonitor(d, timeout_s=60.0)
        assert mon.dead_ranks() == []
        mon_strict = HeartbeatMonitor(d, timeout_s=-1.0)
        assert mon_strict.dead_ranks() == [3]

    def test_straggler_tracker(self):
        t = StragglerTracker(slack=2.0)
        for r in range(8):
            for _ in range(5):
                t.record(r, 1.0)
        for _ in range(5):
            t.record(7, 10.0)
        assert t.stragglers() == [7]


class TestDistributedLowering:
    """pjit on a local 1x1 mesh with the production sharding rules."""

    def test_sharded_train_step_runs(self):
        cfg = get_config("qwen2.5-3b").reduced()
        mesh = make_host_mesh(1, 1)
        a_params, _ = steps_lib.abstract_state(cfg)
        p_sh = shd.param_shardings(mesh, a_params)
        with jax.set_mesh(mesh):
            params = jax.jit(lambda r: lm.init_params(cfg, r), out_shardings=p_sh)(
                jax.random.PRNGKey(0)
            )
            opt_state = adam.AdamState(
                jnp.zeros((), jnp.int32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            )
            step = jax.jit(
                steps_lib.make_train_step(cfg, paper_default(0.8), adam.AdamConfig())
            )
            tok = jnp.zeros((2, 16), jnp.int32)
            params, opt_state, m = step(params, opt_state, {"tokens": tok, "targets": tok})
            assert np.isfinite(float(m["loss"]))

    def test_spec_rules(self):
        """Production rules pick the intended axes."""
        cfg = get_config("mistral-large-123b")
        a_params, _ = steps_lib.abstract_state(cfg)
        specs = shd.param_specs(a_params)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        by_path = {jax.tree_util.keystr(k): v for k, v in flat}
        qw = [v for k, v in by_path.items() if "['attn']['q']['w']" in k]
        assert qw and all(v[-1] == "model" for v in qw)
        ow = [v for k, v in by_path.items() if "['attn']['o']['w']" in k]
        assert ow and all(v[-2] == "model" for v in ow)
        emb = [v for k, v in by_path.items() if "embed" in k]
        assert emb and emb[0][0] == "model"

    def test_moe_expert_parallel_spec(self):
        cfg = get_config("kimi-k2-1t-a32b")
        a_params, _ = steps_lib.abstract_state(cfg)
        specs = shd.param_specs(a_params)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        exp = [v for k, v in flat if "['moe']['up']" in jax.tree_util.keystr(k)]
        # expert tensors are stacked [np, E, d, ff] -> expert axis = model
        assert exp and all(v[1] == "model" for v in exp)

    def test_fit_spec_relocates_illegal_axis(self):
        from jax.sharding import PartitionSpec as P

        class FakeMesh:
            shape = {"model": 16, "data": 16}

        # kv-head dim 8 can't take 16 -> relocated to head_dim 128
        sp = shd.fit_spec(P(None, None, None, "model", None), (9, 128, 32768, 8, 128), FakeMesh())
        assert sp == P(None, None, None, None, "model")
        # batch=1 decode -> relocated to seq dim
        sp = shd.fit_spec(P(None, "data", None, None, "model"), (9, 1, 524288, 8, 128), FakeMesh())
        assert sp == P(None, None, "data", None, "model")


class TestServing:
    def test_serve_driver(self):
        from repro.launch.serve import build_parser, run

        args = build_parser().parse_args(
            ["--arch", "mamba2-1.3b", "--reduced", "--batch", "2",
             "--prompt-len", "4", "--gen", "4"]
        )
        out = run(args)
        assert out["generated"].shape == (2, 4)
        assert out["tokens_per_s"] > 0


def test_compile_cache_location(monkeypatch):
    """The entry points keep compiled programs in a fixed directory:
    JAX_COMPILATION_CACHE_DIR when set (left to JAX, nothing set here),
    else <checkout>/.jax_cache."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
