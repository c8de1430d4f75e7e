"""Unit tests for repro.dist: fit_spec, the spec rule table, fault
tolerance edge cases, and the checkpoint paths test_system.py only
exercises indirectly (partial shardings restore, async-save flush)."""
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np
import pytest

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.registry import get_config
from repro.dist import compat as dist_compat
from repro.dist import sharding as shd
from repro.dist.fault import (
    FleetSupervisor,
    Heartbeat,
    HeartbeatMonitor,
    HeartbeatThread,
    Membership,
    MembershipChanged,
    MembershipView,
    RestartPolicy,
    StragglerEvicted,
    StragglerSupervisor,
    StragglerTracker,
)
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh


class FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


# ----------------------------------------------------------------------
# fit_spec
# ----------------------------------------------------------------------


class TestFitSpec:
    def test_legal_spec_passes_through(self):
        mesh = FakeMesh(model=4, data=2)
        sp = shd.fit_spec(P("data", None, "model"), (8, 3, 16), mesh)
        assert sp == P("data", None, "model")

    def test_relocates_to_nearest_divisible_dim(self):
        mesh = FakeMesh(model=16)
        # 16-way model on dim of size 8: both neighbours legal, later wins
        sp = shd.fit_spec(P(None, "model", None), (32, 8, 32), mesh)
        assert sp == P(None, None, "model")
        # only the earlier neighbour is legal
        sp = shd.fit_spec(P(None, "model", None), (32, 8, 3), mesh)
        assert sp == P("model", None, None)

    def test_no_legal_dim_falls_back_to_replicated(self):
        mesh = FakeMesh(model=16)
        sp = shd.fit_spec(P("model", None), (3, 5), mesh)
        assert sp == P(None, None)

    def test_tuple_axis_splits_jointly(self):
        mesh = FakeMesh(pod=2, data=16)
        # ('pod','data') = 32-way on batch 8: pod (2 | 8) stays on the
        # batch dim, data (16 | 64) relocates to the seq dim — the
        # tuple is split, not moved whole
        sp = shd.fit_spec(P(("pod", "data"), None), (8, 64), mesh)
        assert sp == P("pod", "data")

    def test_tuple_axis_keeps_largest_divisible_subtuple(self):
        mesh = FakeMesh(pod=2, data=16, model=4)
        # batch 16: data (16) wins the batch dim, pod moves to seq
        sp = shd.fit_spec(P(("pod", "data"), None), (16, 4096), mesh)
        assert sp == P("data", "pod")
        # batch 1 decode: nothing divides batch, both relocate; only
        # one free dim remains so the larger axis priority is moot —
        # relocation is per-axis, first-come
        sp = shd.fit_spec(P(("pod", "data"), None), (1, 524288), mesh)
        assert sp == P(None, "pod")

    def test_tuple_axis_whole_tuple_stays_when_divisible(self):
        mesh = FakeMesh(pod=2, data=16)
        sp = shd.fit_spec(P(("pod", "data"), None), (64, 64), mesh)
        assert sp == P(("pod", "data"), None)

    def test_short_spec_is_padded(self):
        mesh = FakeMesh(data=2)
        sp = shd.fit_spec(P("data"), (4, 8, 3), mesh)
        assert sp == P("data", None, None)

    def test_spec_longer_than_shape_is_truncated(self):
        mesh = FakeMesh(model=4)
        sp = shd.fit_spec(P(None, None, "model"), (8, 16), mesh)
        assert sp == P(None, None)

    def test_size_one_axis_always_legal(self):
        mesh = FakeMesh(model=1)
        sp = shd.fit_spec(P("model", None), (3, 5), mesh)
        assert sp == P("model", None)


# ----------------------------------------------------------------------
# param_specs rule table
# ----------------------------------------------------------------------


def _specs_by_path(arch):
    cfg = get_config(arch)
    a_params, _ = steps_lib.abstract_state(cfg)
    specs = shd.param_specs(a_params)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    return {jax.tree_util.keystr(k): v for k, v in flat}


class TestParamSpecs:
    def test_dense_arch_rules(self):
        by_path = _specs_by_path("mistral-large-123b")
        for proj in ("q", "k", "v"):
            vs = [v for k, v in by_path.items() if f"['attn']['{proj}']['w']" in k]
            assert vs and all(v[-1] == "model" for v in vs)
        ow = [v for k, v in by_path.items() if "['attn']['o']['w']" in k]
        assert ow and all(v[-2] == "model" for v in ow)
        up = [v for k, v in by_path.items() if "['mlp']['up']['w']" in k]
        assert up and all(v[-1] == "model" for v in up)
        dn = [v for k, v in by_path.items() if "['mlp']['down']['w']" in k]
        assert dn and all(v[-2] == "model" for v in dn)
        norms = [v for k, v in by_path.items() if "norm" in k]
        assert norms and all(all(e is None for e in v) for v in norms)

    def test_moe_arch_rules(self):
        by_path = _specs_by_path("kimi-k2-1t-a32b")
        for t in ("gate", "up", "down"):
            vs = [v for k, v in by_path.items() if f"['moe']['{t}']" in k and "shared" not in k]
            assert vs and all(v[1] == "model" for v in vs)
        router = [v for k, v in by_path.items() if "router" in k]
        assert router and all(all(e is None for e in v) for v in router)

    def test_ssm_arch_rules(self):
        by_path = _specs_by_path("mamba2-1.3b")
        inp = [v for k, v in by_path.items() if "['in_proj']['w']" in k]
        assert inp and all(v[-1] == "model" for v in inp)
        outp = [v for k, v in by_path.items() if "['out_proj']['w']" in k]
        assert outp and all(v[-2] == "model" for v in outp)
        conv = [v for k, v in by_path.items() if "conv" in k]
        assert conv and all(all(e is None for e in v) for v in conv)

    def test_embed_sharded_on_vocab(self):
        for arch in ("mistral-large-123b", "kimi-k2-1t-a32b", "mamba2-1.3b"):
            by_path = _specs_by_path(arch)
            emb = [v for k, v in by_path.items() if "embed" in k]
            assert emb and emb[0][0] == "model"

    def test_replicate_kv_option(self):
        cfg = get_config("qwen2.5-3b")
        a_params, _ = steps_lib.abstract_state(cfg)
        specs = shd.param_specs(a_params, replicate_kv=True)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        by_path = {jax.tree_util.keystr(k): v for k, v in flat}
        for proj, expect_model in (("k", False), ("v", False), ("q", True)):
            vs = [v for k, v in by_path.items() if f"['attn']['{proj}']['w']" in k]
            assert vs
            for v in vs:
                assert (v[-1] == "model") == expect_model

    def test_param_shardings_all_legal_on_host_mesh(self):
        mesh = make_host_mesh(1, 1)
        cfg = get_config("qwen2.5-3b").reduced()
        a_params, _ = steps_lib.abstract_state(cfg)
        shardings = shd.param_shardings(mesh, a_params)
        leaves = jax.tree.leaves(shardings)
        assert leaves and all(
            isinstance(s, jax.sharding.NamedSharding) for s in leaves
        )


# ----------------------------------------------------------------------
# fault tolerance edge cases
# ----------------------------------------------------------------------


class TestHeartbeat:
    def test_empty_dir_no_dead_ranks(self, tmp_path):
        mon = HeartbeatMonitor(str(tmp_path), timeout_s=0.0)
        assert mon.dead_ranks() == []
        # a directory that doesn't exist yet is also fine
        mon = HeartbeatMonitor(str(tmp_path / "missing"), timeout_s=0.0)
        assert mon.dead_ranks() == []

    def test_single_rank_alive_then_dead(self, tmp_path):
        d = str(tmp_path)
        hb = Heartbeat(d, rank=0, interval_s=0.0)
        hb.beat(force=True)
        assert HeartbeatMonitor(d, timeout_s=3600.0).dead_ranks() == []
        assert HeartbeatMonitor(d, timeout_s=-1.0).dead_ranks() == [0]

    def test_interval_throttles_beats(self, tmp_path):
        hb = Heartbeat(str(tmp_path), rank=1, interval_s=3600.0)
        assert hb.beat() is True
        assert hb.beat() is False  # throttled
        assert hb.beat(force=True) is True

    def test_foreign_files_ignored(self, tmp_path):
        d = str(tmp_path)
        (tmp_path / "rank_notanumber").write_text("x")
        (tmp_path / "unrelated.txt").write_text("x")
        Heartbeat(d, rank=2, interval_s=0.0).beat(force=True)
        assert HeartbeatMonitor(d, timeout_s=-1.0).dead_ranks() == [2]


class TestStragglerTracker:
    def test_single_rank_never_straggles(self):
        t = StragglerTracker(slack=2.0)
        for _ in range(10):
            t.record(0, 100.0)
        assert t.stragglers() == []

    def test_warmup_records_not_judged(self):
        t = StragglerTracker(slack=2.0, min_records=3)
        t.record(0, 1.0)
        t.record(1, 50.0)
        assert t.stragglers() == []

    def test_slack_boundary(self):
        # EWMA exactly at slack x median is NOT a straggler; above is.
        t = StragglerTracker(slack=2.0, alpha=1.0, min_records=1)
        for r in (0, 1, 2):
            t.record(r, 1.0)
        t.record(3, 2.0)
        assert t.stragglers() == []  # 2.0 == 2.0 * median(1.0)
        t.record(3, 2.0 + 1e-6)
        assert t.stragglers() == [3]

    def test_two_rank_fleet_flags_the_slow_rank(self):
        # leave-one-out baseline: the slow rank must not shift the
        # median it is judged against
        t = StragglerTracker(slack=2.0, alpha=1.0, min_records=1)
        t.record(0, 1.0)
        t.record(1, 1000.0)
        assert t.stragglers() == [1]

    def test_recovered_rank_drops_off(self):
        t = StragglerTracker(slack=2.0, alpha=1.0, min_records=1)
        for r in range(4):
            t.record(r, 1.0)
        t.record(3, 10.0)
        assert t.stragglers() == [3]
        t.record(3, 1.0)  # alpha=1.0 -> instant recovery
        assert t.stragglers() == []


class TestStragglerEviction:
    """ROADMAP "Straggler response": detection wired to RestartPolicy
    through an excluded-rank list."""

    @staticmethod
    def _sup(patience=3):
        return StragglerSupervisor(
            StragglerTracker(slack=2.0, alpha=1.0, min_records=1),
            patience=patience,
        )

    def _feed(self, sup, slow_rank=3, slow=10.0, ranks=4):
        for r in range(ranks):
            sup.record(r, slow if r == slow_rank else 1.0)

    def test_patience_gates_eviction(self):
        sup = self._sup(patience=3)
        for _ in range(2):
            self._feed(sup)
            sup.check()  # streaks 1, 2: no eviction yet
        self._feed(sup)
        with pytest.raises(StragglerEvicted) as ei:
            sup.check()
        assert ei.value.rank == 3
        assert ei.value.ewma_s > ei.value.baseline_s

    def test_transient_slowness_resets_streak(self):
        sup = self._sup(patience=2)
        self._feed(sup)
        sup.check()
        self._feed(sup, slow=1.0)  # alpha=1.0: instant recovery
        sup.check()  # streak cleared
        self._feed(sup)
        sup.check()  # streak back to 1 — still no eviction
        self._feed(sup)
        with pytest.raises(StragglerEvicted):
            sup.check()

    def test_excluded_rank_never_re_evicted(self):
        sup = self._sup(patience=1)
        for _ in range(5):
            self._feed(sup)
            sup.check(excluded=[3])  # must not raise

    def test_restart_policy_records_rank_and_reshards(self):
        pol = RestartPolicy(max_restarts=0, backoff_s=0.0)
        seen = []

        def attempt(i):
            seen.append(tuple(pol.excluded_ranks))
            if not pol.excluded_ranks:
                raise StragglerEvicted(3, 10.0, 1.0)
            return "ok"

        evicted = []
        assert pol.run(attempt, on_evict=lambda r, e: evicted.append(r)) == "ok"
        assert pol.excluded_ranks == [3]
        assert evicted == [3]
        assert seen == [(), (3,)]  # second attempt saw the eviction

    def test_eviction_does_not_consume_restart_budget(self):
        pol = RestartPolicy(max_restarts=1, backoff_s=0.0)
        calls = []

        def attempt(i):
            calls.append(i)
            if len(calls) == 1:
                raise StragglerEvicted(1, 5.0, 1.0)
            if len(calls) == 2:
                raise RuntimeError("boom")
            return "ok"

        # one eviction + one crash still succeeds on a budget of 1
        assert pol.run(attempt) == "ok"
        assert len(calls) == 3

    def test_double_eviction_degrades_to_bounded_restart(self):
        pol = RestartPolicy(max_restarts=0, backoff_s=0.0)

        def attempt(i):
            raise StragglerEvicted(2, 9.0, 1.0)

        with pytest.raises(StragglerEvicted):
            pol.run(attempt)
        assert pol.excluded_ranks == [2]  # added once, then budget-bounded

    def test_evicted_rank_ewma_does_not_mask_survivors(self):
        # rank 2 evicted at EWMA 10.0; its stale entry must not inflate
        # the baseline rank 1 is judged against afterwards
        sup = self._sup(patience=1)
        sup.record(0, 1.0)
        sup.record(1, 1.0)
        sup.record(2, 10.0)
        with pytest.raises(StragglerEvicted) as ei:
            sup.check()
        assert ei.value.rank == 2
        sup.record(0, 1.0)
        sup.record(1, 3.9)  # straggler vs median 1.0 — but not vs 5.5
        with pytest.raises(StragglerEvicted) as ei:
            sup.check(excluded=[2])
        assert ei.value.rank == 1

    def test_eviction_storm_is_bounded(self):
        # never-repeating rank ids must not grant unlimited free restarts
        pol = RestartPolicy(max_restarts=0, backoff_s=0.0, max_evictions=3)
        seen = {"n": 0}

        def attempt(i):
            seen["n"] += 1
            raise StragglerEvicted(seen["n"], 9.0, 1.0)

        with pytest.raises(StragglerEvicted):
            pol.run(attempt)
        # 3 budgeted evictions + the one that degraded to a bounded restart
        assert len(pol.excluded_ranks) == 4

    def test_eviction_path_end_to_end(self):
        sup = self._sup(patience=2)
        pol = RestartPolicy(max_restarts=0, backoff_s=0.0)

        def attempt(i):
            ranks = [r for r in range(4) if r not in pol.excluded_ranks]
            for _ in range(3):
                for r in ranks:
                    sup.record(r, 10.0 if r == 2 else 1.0)
                sup.check(excluded=pol.excluded_ranks)
            return ranks

        assert pol.run(attempt) == [0, 1, 3]
        assert pol.excluded_ranks == [2]


class TestRestartPolicy:
    def test_retries_then_succeeds(self):
        calls = []

        def attempt(i):
            calls.append(i)
            if i < 2:
                raise RuntimeError("boom")
            return "ok"

        pol = RestartPolicy(max_restarts=3, backoff_s=0.0)
        restarts = []
        out = pol.run(attempt, on_restart=lambda i, e: restarts.append(i))
        assert out == "ok"
        assert calls == [0, 1, 2]
        assert restarts == [0, 1]

    def test_exhausted_restarts_reraise(self):
        pol = RestartPolicy(max_restarts=1, backoff_s=0.0)
        with pytest.raises(RuntimeError, match="always"):
            pol.run(lambda i: (_ for _ in ()).throw(RuntimeError("always")))


# ----------------------------------------------------------------------
# checkpoint: partial shardings restore + async-save flush
# ----------------------------------------------------------------------


class TestCkptPaths:
    def test_restore_with_partial_shardings(self, tmp_path):
        d = str(tmp_path)
        params = {"w": jnp.arange(8.0).reshape(2, 4)}
        m = {"w": jnp.ones((2, 4))}
        v = {"w": jnp.full((2, 4), 2.0)}
        ckpt_lib.save(d, 3, {"params": params, "m": m, "v": v})

        mesh = make_host_mesh(1, 1)
        sh = jax.sharding.NamedSharding(mesh, P(None, None))
        like = {"params": params, "m": m, "v": v}
        # partial: only params carries a sharding; m/v restore unsharded
        r = ckpt_lib.restore(d, 3, like, shardings={"params": {"w": sh}})
        np.testing.assert_array_equal(np.asarray(r["params"]["w"]), params["w"])
        np.testing.assert_array_equal(np.asarray(r["m"]["w"]), m["w"])
        np.testing.assert_array_equal(np.asarray(r["v"]["w"]), v["w"])
        assert r["params"]["w"].sharding.is_equivalent_to(sh, 2)

    def test_restore_rejects_unmatched_shardings_keys(self, tmp_path):
        d = str(tmp_path)
        ckpt_lib.save(d, 1, {"params": {"w": jnp.ones(4)}})
        mesh = make_host_mesh(1, 1)
        sh = jax.sharding.NamedSharding(mesh, P(None))
        with pytest.raises(ValueError, match="match no checkpoint leaf"):
            ckpt_lib.restore(
                d, 1, {"params": {"w": jnp.ones(4)}},
                shardings={"param": {"w": sh}},  # typo'd key
            )

    def test_restore_with_single_sharding_broadcast(self, tmp_path):
        d = str(tmp_path)
        tree = {"a": jnp.ones(4), "b": jnp.zeros((2, 2))}
        ckpt_lib.save(d, 1, tree)
        mesh = make_host_mesh(1, 1)
        sh = jax.sharding.NamedSharding(mesh, P())
        r = ckpt_lib.restore(d, 1, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(r["a"]), tree["a"])
        np.testing.assert_array_equal(np.asarray(r["b"]), tree["b"])

    def test_saver_wait_flushes_last_async_save(self, tmp_path):
        d = str(tmp_path)
        saver = ckpt_lib.Saver(d, keep=10)
        for s in (1, 2, 3):
            saver.save(s, {"x": jnp.full((4,), float(s))})
        saver.wait()
        assert saver.last_path is not None
        assert ckpt_lib.list_steps(d) == [1, 2, 3]
        r = ckpt_lib.restore(d, 3, {"x": jnp.zeros(4)})
        np.testing.assert_array_equal(np.asarray(r["x"]), np.full((4,), 3.0))

    def test_saver_wait_idempotent_and_safe_before_save(self, tmp_path):
        saver = ckpt_lib.Saver(str(tmp_path))
        saver.wait()  # no save in flight: must not raise
        saver.save(1, {"x": jnp.ones(2)})
        saver.wait()
        saver.wait()
        assert ckpt_lib.latest_step(str(tmp_path)) == 1


# ----------------------------------------------------------------------
# clock skew: heartbeat mtimes vs the monitor's wall clock
# ----------------------------------------------------------------------


class TestMonitorClockSkew:
    def test_skewed_monitor_clock_does_not_evict_live_ranks(
        self, tmp_path, monkeypatch
    ):
        """Regression: ``dead_ranks()`` used to compare file mtimes
        against the monitor host's ``time.time()``; a monitor running
        ahead of the file server's clock falsely evicted live ranks.
        The default ``now`` is a sentinel-file mtime from the SAME
        filesystem clock, so process-clock skew is invisible."""
        import time as _time

        hb = Heartbeat(str(tmp_path), rank=0, interval_s=0.0)
        hb.beat(force=True)
        mon = HeartbeatMonitor(str(tmp_path), timeout_s=5.0)

        real = _time.time
        monkeypatch.setattr(_time, "time", lambda: real() + 10_000.0)
        assert mon.dead_ranks() == []

    def test_skewed_monitor_clock_behind_still_detects_dead(
        self, tmp_path, monkeypatch
    ):
        """The converse skew (monitor clock behind the file server)
        must not mask a genuinely stale heartbeat."""
        import os as _os
        import time as _time

        hb = Heartbeat(str(tmp_path), rank=0, interval_s=0.0)
        hb.beat(force=True)
        # fake a rank that stopped beating 100s ago (skewed mtimes)
        past = _os.path.getmtime(hb.path) - 100.0
        _os.utime(hb.path, (past, past))
        mon = HeartbeatMonitor(str(tmp_path), timeout_s=5.0)

        real = _time.time
        monkeypatch.setattr(_time, "time", lambda: real() - 10_000.0)
        assert mon.dead_ranks() == [0]

    def test_explicit_now_overrides_sentinel(self, tmp_path):
        import os as _os

        hb = Heartbeat(str(tmp_path), rank=3, interval_s=0.0)
        hb.beat(force=True)
        mon = HeartbeatMonitor(str(tmp_path), timeout_s=5.0)
        mtime = _os.path.getmtime(hb.path)
        assert mon.dead_ranks(now=mtime + 1.0) == []
        assert mon.dead_ranks(now=mtime + 100.0) == [3]


class TestHeartbeatThread:
    def test_background_beater_keeps_beating_through_main_stall(
        self, tmp_path
    ):
        """The beater thread models a rank whose MAIN thread is stuck
        in a long XLA compile: the heartbeat must stay fresh anyway
        (process liveness, not step progress)."""
        import os as _os
        import time as _time

        hb = Heartbeat(str(tmp_path), rank=0, interval_s=0.05)
        t = HeartbeatThread(hb).start()
        try:
            first = _os.path.getmtime(hb.path)
            deadline = _time.monotonic() + 5.0
            while _os.path.getmtime(hb.path) <= first:
                assert _time.monotonic() < deadline, "beater never beat again"
                _time.sleep(0.05)  # the "stalled" main thread
        finally:
            t.stop()

    def test_stop_is_graceful_and_idempotent(self, tmp_path):
        hb = Heartbeat(str(tmp_path), rank=1, interval_s=0.05)
        t = HeartbeatThread(hb).start()
        t.stop()
        t.stop()
        assert not t._thread.is_alive()


# ----------------------------------------------------------------------
# membership epochs: evict / un-evict / leader failover
# ----------------------------------------------------------------------


class TestMembership:
    def test_evict_bumps_epoch_and_moves_rank(self):
        m = Membership(0, (0, 1, 2, 3), ())
        m2 = m.evict([2])
        assert (m2.epoch, m2.active, m2.evicted) == (1, (0, 1, 3), (2,))

    def test_evict_noop_for_inactive_rank_keeps_epoch(self):
        m = Membership(0, (0, 1), (2,))
        assert m.evict([2]) is m
        assert m.evict([7]) is m

    def test_unevict_restores_rank_and_bumps_epoch(self):
        m = Membership(1, (0, 1, 3), (2,))
        m2 = m.unevict([2])
        assert (m2.epoch, m2.active, m2.evicted) == (2, (0, 1, 2, 3), ())

    def test_leader_fails_over_deterministically(self):
        m = Membership(0, (0, 1, 2), ())
        assert m.leader == 0
        assert m.evict([0]).leader == 1
        assert m.evict([0, 1]).leader == 2
        assert m.evict([0, 1, 2]).leader == -1

    def test_view_roundtrip_and_initial(self, tmp_path):
        view = MembershipView(str(tmp_path), 4)
        assert view.read() == view.initial() == Membership(0, (0, 1, 2, 3), ())
        m = view.initial().evict([1])
        view.write(m)
        assert view.read() == m


class TestFleetSupervisor:
    def _beat_all(self, coord, ranks):
        for r in ranks:
            Heartbeat(str(coord / "hb"), rank=r, interval_s=0.0).beat(force=True)

    def _stale(self, coord, rank, ago=100.0):
        import os as _os

        path = str(coord / "hb" / f"rank_{rank:05d}")
        past = _os.path.getmtime(path) - ago
        _os.utime(path, (past, past))

    def test_poll_evicts_stale_rank(self, tmp_path):
        self._beat_all(tmp_path, range(3))
        sup = FleetSupervisor(str(tmp_path), 3, timeout_s=5.0)
        self._stale(tmp_path, 2)
        m = sup.poll()
        assert (m.epoch, m.active, m.evicted) == (1, (0, 1), (2,))

    def test_poll_evicts_rank_that_never_beat(self, tmp_path):
        self._beat_all(tmp_path, [0, 2])
        sup = FleetSupervisor(str(tmp_path), 3, timeout_s=5.0)
        m = sup.poll()
        assert m.evicted == (1,)

    def test_rejoin_needs_request_and_fresh_beat(self, tmp_path):
        self._beat_all(tmp_path, range(2))
        sup = FleetSupervisor(str(tmp_path), 2, timeout_s=5.0)
        self._stale(tmp_path, 1)
        assert sup.poll().evicted == (1,)

        # a rejoin request alone (beat still stale) is not enough: a
        # stale request file from a rank that died again must not flap
        sup.request_rejoin(1)
        assert sup.poll().evicted == (1,)

        # fresh beat + request ⇒ un-evicted, epoch bumped again
        self._beat_all(tmp_path, [1])
        m = sup.poll()
        assert (m.epoch, m.active, m.evicted) == (2, (0, 1), ())
        # the request was consumed: the next poll is a no-op
        assert sup.poll().epoch == 2

    def test_completed_rank_is_never_evicted(self, tmp_path):
        """Orderly leave: a rank that wrote its done marker stops
        heartbeating on purpose — silence is completion, not death."""
        self._beat_all(tmp_path, range(2))
        (tmp_path / "done").mkdir()
        (tmp_path / "done" / "rank_00001.json").write_text("{}")
        sup = FleetSupervisor(str(tmp_path), 2, timeout_s=5.0)
        self._stale(tmp_path, 1)
        m = sup.poll()
        assert (m.epoch, m.active, m.evicted) == (0, (0, 1), ())
        assert sup.completed_ranks() == [1]

    def test_check_epoch_raises_on_drift(self, tmp_path):
        self._beat_all(tmp_path, range(2))
        sup = FleetSupervisor(str(tmp_path), 2, timeout_s=5.0)
        assert sup.check_epoch(0).epoch == 0
        self._stale(tmp_path, 1)
        sup.poll()
        with pytest.raises(MembershipChanged) as exc:
            sup.check_epoch(0)
        assert exc.value.membership.epoch == 1

    def test_should_poll_leader_and_failover(self, tmp_path):
        self._beat_all(tmp_path, range(3))
        sup = FleetSupervisor(str(tmp_path), 3, timeout_s=5.0)
        assert sup.should_poll(0)
        assert not sup.should_poll(1)
        assert not sup.should_poll(2)
        # leader heartbeat goes stale: the NEXT rank inherits the seat
        # (exactly one standby — rank 2 still defers)
        self._stale(tmp_path, 0)
        assert sup.should_poll(1)
        assert not sup.should_poll(2)

    def test_should_poll_skips_completed_leader(self, tmp_path):
        self._beat_all(tmp_path, range(3))
        (tmp_path / "done").mkdir()
        (tmp_path / "done" / "rank_00000.json").write_text("{}")
        sup = FleetSupervisor(str(tmp_path), 3, timeout_s=5.0)
        # rank 0 finished: the lowest still-running rank is the leader
        assert not sup.should_poll(0)
        assert sup.should_poll(1)
        assert not sup.should_poll(2)

    def test_wait_active_times_out_with_actionable_error(self, tmp_path):
        self._beat_all(tmp_path, range(2))
        sup = FleetSupervisor(str(tmp_path), 2, timeout_s=5.0)
        self._stale(tmp_path, 1)
        sup.poll()
        with pytest.raises(TimeoutError, match="rank 1 never re-admitted"):
            sup.wait_active(1, timeout_s=0.1)


class TestRestartPolicyUnexclude:
    def test_unexclude_readmits_and_reports(self):
        p = RestartPolicy(max_restarts=0)
        p.excluded_ranks.append(3)
        assert p.unexclude(3) is True
        assert p.excluded_ranks == []
        assert p.unexclude(3) is False

    def test_unexcluded_rank_is_evictable_afresh(self):
        """The rejoin half of the protocol: after unexclude, a repeat
        eviction of the same rank must again restart budget-free."""
        p = RestartPolicy(max_restarts=0, backoff_s=0.0)
        calls = []

        def attempt(i):
            calls.append(i)
            if len(calls) == 1:
                raise StragglerEvicted(3, 1.0, 0.1)
            if len(calls) == 2:
                p.unexclude(3)
                raise StragglerEvicted(3, 1.0, 0.1)
            return "ok"

        assert p.run(attempt) == "ok"
        assert len(calls) == 3


# ----------------------------------------------------------------------
# ProcessGroup: filesystem-backed control-plane collectives
# ----------------------------------------------------------------------


class TestProcessGroup:
    def _group(self, tmp_path, world=2, **kw):
        return [
            dist_compat.ProcessGroup(str(tmp_path), r, world, **kw)
            for r in range(world)
        ]

    def test_put_get_roundtrip(self, tmp_path):
        a, b = self._group(tmp_path)
        a.put("x.0", {"v": 1})
        assert b.get("x.0", 0, timeout_s=1.0) == {"v": 1}
        assert b.try_get("x.0", 1) is None

    def test_gather_returns_every_participant(self, tmp_path):
        a, b = self._group(tmp_path)
        a.put("g.0", "from0")
        got = b.gather("g.0", "from1", timeout_s=1.0)
        assert got == {0: "from0", 1: "from1"}

    def test_collectives_among_survivor_subset(self, tmp_path):
        """After an eviction the survivors pass ``ranks=`` and never
        wait on the dead rank."""
        pgs = self._group(tmp_path, world=3)
        pgs[0].put("s.0", 0)
        got = pgs[2].gather("s.0", 2, ranks=[0, 2], timeout_s=1.0)
        assert got == {0: 0, 2: 2}
        pgs[0].put("bar.b.0", None)
        pgs[2].barrier("b.0", ranks=[0, 2], timeout_s=1.0)

    def test_broadcast_from_src(self, tmp_path):
        a, b = self._group(tmp_path)
        a.broadcast("cfg.0", {"seed": 7})
        assert b.broadcast("cfg.0", src=0, timeout_s=1.0) == {"seed": 7}

    def test_missing_peer_times_out_not_hangs(self, tmp_path):
        (a,) = self._group(tmp_path, world=1)
        pg = dist_compat.ProcessGroup(str(tmp_path), 0, 2)
        with pytest.raises(dist_compat.ProcessGroupTimeout, match="rank 1"):
            pg.get("never.0", 1, timeout_s=0.05)

    def test_rank_outside_world_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="outside world"):
            dist_compat.ProcessGroup(str(tmp_path), 5, 2)

    def test_initialize_registers_and_unblocks(self, tmp_path):
        """initialize blocks until every peer registers, so the two
        ranks must initialize concurrently (as real processes would)."""
        import threading

        d = str(tmp_path)
        pgs = {}

        def init(r):
            pgs[r] = dist_compat.initialize(
                d, process_id=r, num_processes=2, timeout_s=10.0
            )

        threads = [threading.Thread(target=init, args=(r,)) for r in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15.0)
        assert sorted(pgs) == [0, 1]
        assert dist_compat.registered_ranks(d) == [0, 1]
        pgs[0].put("hello.0", "hi")
        assert pgs[1].get("hello.0", 0, timeout_s=1.0) == "hi"

    def test_registrations_record_host_and_platform(self, tmp_path):
        dist_compat.initialize(
            str(tmp_path), process_id=0, num_processes=1, timeout_s=1.0
        )
        reg = dist_compat.registrations(str(tmp_path))[0]
        assert reg["platform"] == jax.default_backend()
        assert reg["host"] and reg["local_devices"] >= 1

    @pytest.mark.parametrize(
        "hosts,platforms,refused",
        [
            (("a", "a"), ("cpu", "cpu"), False),  # the chaos harness
            (("a", "a"), ("tpu", "tpu"), True),   # ranks fight over chips
            (("a", "a"), ("tpu", "cpu"), True),   # one fell back to the CPU
            (("a", "b"), ("tpu", "tpu"), False),  # one rank per TPU host
        ],
    )
    def test_fleet_refuses_ranks_sharing_a_tpu_host(
        self, tmp_path, hosts, platforms, refused
    ):
        from repro.launch import train

        os.makedirs(tmp_path / "ranks")
        for r, (host, plat) in enumerate(zip(hosts, platforms, strict=True)):
            with open(tmp_path / "ranks" / f"rank_{r:05d}.json", "w") as f:
                json.dump({"pid": r, "host": host, "platform": plat}, f)
        for rank in (0, 1):
            if refused:
                with pytest.raises(RuntimeError, match="one rank per host"):
                    train.refuse_shared_chips(str(tmp_path), rank)
            else:
                train.refuse_shared_chips(str(tmp_path), rank)

    def test_initialize_times_out_on_missing_peer(self, tmp_path):
        with pytest.raises(
            dist_compat.ProcessGroupTimeout, match="never registered"
        ):
            dist_compat.initialize(
                str(tmp_path), process_id=0, num_processes=2, timeout_s=0.1
            )
