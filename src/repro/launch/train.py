"""Fault-tolerant LM training driver (end-to-end, any --arch).

Wires together: config registry → synthetic data pipeline → sharded
params/optimizer → a resolved ssProp **policy program** (per-site rules
× schedule; the paper's bar schedule compiles to two executables: dense
epoch / sparse epoch) → async checkpointing → heartbeat + restart
policy. On restart it resumes from the latest committed checkpoint; the
pure-function-of-step data pipeline makes the replay exact.

The sparsity control surface is ONE object: a
:class:`repro.core.policy.PolicyProgram` built from ``--rules`` (the
``pattern=rate;...`` mini-grammar over the model's site names, see
``docs/policies.md``) and ``--scheduler``; the loop just asks
``resolved.policies_for_step(step)``.

**Multi-process mode** (``--coord-dir`` + ``--world-size N`` +
``--rank r``): every rank runs this driver as its own OS process
against a shared coordination directory. Each rank heartbeats, the
leader (lowest active rank) runs the :class:`FleetSupervisor` poll,
and every step is guarded by a membership-epoch check — a stale rank
is evicted (epoch bump), survivors abort with ``MembershipChanged``
and restart resharded from the last committed checkpoint, and a
relaunched rank rejoins through the un-evict protocol. Checkpoints
are **per-host sharded**: each rank writes only ``shard_<r>.msgpack``
and the leader commits once every active peer's shard lands.

Compute is replicated across ranks (every rank steps the full global
batch): loss trajectories are bit-identical at any fleet size, which
is what lets the chaos tests assert kill → shrink → rejoin leaves the
trajectory exactly equal to an uninterrupted run. The *distributed*
state — membership epochs, shard plans, commit barriers — is the real
multi-host protocol. See ``docs/distributed.md``. Each rank process
holds its host's devices, so on an accelerator the fleet runs one rank
per host; ranks that share a TPU host are refused at start-up
(:func:`refuse_shared_chips`), and the chaos harness runs on the CPU.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      --reduced --steps 50 --ckpt-dir /tmp/run1
  # per-site: first/last layer dense, attention at 0.5, the rest at 0.8
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      --reduced --steps 50 --no-scan-layers \
      --rules 'layer_{0,-1}/*=dense;*/attn/*=0.5;*=0.8'
  # crash/resume: re-running the same command continues from the latest
  # checkpoint.
  # 4-rank fleet on one machine (each line its own process):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
      --reduced --steps 50 --ckpt-dir /tmp/fleet/ckpt \
      --coord-dir /tmp/fleet --world-size 4 --rank 0  # ... rank 1..3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.base import ModelConfig
from repro.core.policy import (
    PolicyProgram,
    PolicyRules,
    ResolvedProgram,
    paper_default,
    tpu_default,
)
from repro.core.schedulers import make_schedule
from repro.data.pipeline import TokenPipeline, TokenPipelineConfig
from repro.dist import compat as dist_compat
from repro.dist import sharding as shd
from repro.dist.fault import (
    FleetSupervisor,
    Heartbeat,
    HeartbeatThread,
    RestartPolicy,
    StragglerSupervisor,
)
from repro.launch import compile_cache
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_host_mesh
from repro.models import model as lm
from repro.optim import adam


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--drop-rate", type=float, default=0.8)
    ap.add_argument("--scheduler", default="epoch_bar")
    ap.add_argument("--steps-per-epoch", type=int, default=10)
    ap.add_argument("--period", type=int, default=100,
                    help="periodic_bar scheduler period (iterations)")
    ap.add_argument("--granularity", choices=["channel", "block"], default="channel")
    ap.add_argument("--rules", default="",
                    help="per-site rules 'pattern=rate;...' over the model's "
                         "site names (rate may be 'dense'); empty = one "
                         "global rule at --drop-rate")
    ap.add_argument("--no-scan-layers", action="store_true",
                    help="unroll the layer stack (required for per-depth "
                         "rules like layer_{0,-1}/*)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a crash once (fault-tolerance demo/test)")
    # multi-process fleet (see module docstring / docs/distributed.md)
    ap.add_argument("--coord-dir", default="",
                    help="shared coordination dir; with --world-size > 1 "
                         "enables the rank-complete fault protocol and "
                         "per-host sharded checkpoints")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world-size", type=int, default=1)
    ap.add_argument("--hb-interval", type=float, default=1.0,
                    help="seconds between heartbeat touches")
    ap.add_argument("--hb-timeout", type=float, default=5.0,
                    help="heartbeat staleness before eviction")
    ap.add_argument("--commit-timeout", type=float, default=30.0,
                    help="leader wait for peers' checkpoint shards")
    ap.add_argument("--rejoin-timeout", type=float, default=60.0,
                    help="evicted rank's wait to be re-admitted")
    ap.add_argument("--step-delay", type=float, default=0.0,
                    help="sleep per step (chaos tests: stretch the run "
                         "so a kill lands mid-training)")
    return ap


def build_program(args, base_policy) -> PolicyProgram:
    """The one control surface: rules (site patterns) × schedule."""
    schedule = make_schedule(
        args.scheduler,
        target=args.drop_rate,
        total_steps=args.steps,
        steps_per_epoch=args.steps_per_epoch,
        period=args.period,
        rate_buckets=base_policy.rate_buckets,
    )
    if args.rules:
        rules = PolicyRules.parse(args.rules, base=base_policy)
    else:
        rules = PolicyRules.single(base_policy)
    return PolicyProgram(rules=rules, schedule=schedule)


def init_sharded_params(cfg, mesh, seed: int):
    """The weights from ``seed``, built on the device in ``mesh``'s
    production placement (:mod:`repro.dist.sharding`)."""
    a_params, _ = steps_lib.abstract_state(cfg)
    return jax.jit(
        lambda r: lm.init_params(cfg, r),
        out_shardings=shd.param_shardings(mesh, a_params),
    )(jax.random.PRNGKey(seed))


def init_train_state(cfg, mesh, seed: int):
    """(params, Adam state) from ``seed``, built on the device in ``mesh``'s
    production placement (:mod:`repro.dist.sharding`)."""
    a_params, _ = steps_lib.abstract_state(cfg)
    opt_sh = shd.opt_state_shardings(mesh, a_params)
    params = init_sharded_params(cfg, mesh, seed)
    zeros = jax.jit(
        lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
        out_shardings=opt_sh,
    )
    return params, adam.AdamState(
        step=jnp.zeros((), jnp.int32), m=zeros(params), v=zeros(params)
    )


def refuse_shared_chips(coord_dir: str, rank: int) -> None:
    """Fleet mode runs every rank as its own process holding its own
    devices. An accelerator belongs to one process at a time, so ranks
    that share a host can only run on the CPU (the chaos harness)."""
    regs = dist_compat.registrations(coord_dir)
    me = regs[rank]
    peers = sorted(
        r for r, reg in regs.items()
        if r != rank and reg.get("host") == me.get("host")
    )
    if peers and any(
        regs[r].get("platform", "cpu") != "cpu" for r in (rank, *peers)
    ):
        raise RuntimeError(
            f"fleet ranks {sorted((rank, *peers))} share host "
            f"{me.get('host')!r} on a {me.get('platform')} backend: its "
            "chips belong to one process, so run one rank per host (or "
            "the whole fleet with JAX_PLATFORMS=cpu)"
        )


@dataclasses.dataclass
class Trainer:
    """What :func:`run` trains with, built apart from the loop (:func:`build`)
    so that a benchmark drives the same step on the same state."""

    cfg: ModelConfig
    mesh: jax.sharding.Mesh
    pipe: TokenPipeline
    program: PolicyProgram
    resolved: ResolvedProgram
    opt_cfg: adam.AdamConfig
    seed: int
    steps: dict = dataclasses.field(default_factory=dict)

    def step_for(self, step: int):
        """The jitted step (params and optimizer state donated) that
        ``step`` runs. One executable per schedule bucket (paper: 2 for
        epoch_bar); the step's SitePolicies table is the cache key, so
        per-site programs cost no extra retraces beyond the buckets."""
        table = self.resolved.policies_for_step(step)
        if table not in self.steps:
            fn = steps_lib.make_train_step(self.cfg, table, self.opt_cfg)
            # the new state keeps the placement it came in: a step whose
            # outputs were left to the compiler returned some of them
            # placed otherwise, and every later call compiled again
            p_sh = shd.param_shardings(self.mesh, steps_lib.abstract_state(self.cfg)[0])
            state_sh = (p_sh, adam.AdamState(shd.replicated(self.mesh), p_sh, p_sh))
            self.steps[table] = jax.jit(
                fn, donate_argnums=(0, 1), out_shardings=(*state_sh, None)
            )
        return self.steps[table]


def build(args) -> Trainer:
    """The model, mesh, data pipeline, policy program and optimizer that
    ``args`` (:func:`build_parser`) describe. Every site whose output
    channels the mesh splits (column-parallel projections) selects per
    shard (``tp_shards`` = the ``model`` degree); the others globally."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if getattr(args, "no_scan_layers", False):
        cfg = dataclasses.replace(cfg, scan_layers=False)
    mesh = make_host_mesh(args.data_mesh, args.model_mesh)
    pipe = TokenPipeline(
        TokenPipelineConfig(cfg.vocab, args.seq_len, args.global_batch, args.seed)
    )
    base_policy = (
        paper_default(args.drop_rate)
        if args.granularity == "channel"
        else tpu_default(args.drop_rate)
    )
    program = build_program(args, base_policy)
    sites, depth = lm.site_names(cfg)
    resolved = program.resolve(sites, depth=depth).with_tp_shards(
        lambda site: shd.output_shards(mesh, site)
    )
    opt_cfg = adam.AdamConfig(lr=args.lr, clip_norm=1.0, total_steps=args.steps)
    return Trainer(cfg, mesh, pipe, program, resolved, opt_cfg, args.seed)


def run(args) -> dict:
    trainer = build(args)
    cfg, mesh, pipe, program = trainer.cfg, trainer.mesh, trainer.pipe, trainer.program

    a_params, _ = steps_lib.abstract_state(cfg)
    p_sh = shd.param_shardings(mesh, a_params)
    opt_sh = shd.opt_state_shardings(mesh, a_params)

    ckpt_dir = args.ckpt_dir
    rank = getattr(args, "rank", 0)
    world = getattr(args, "world_size", 1)
    coord_dir = getattr(args, "coord_dir", "")
    multi = bool(coord_dir) and world > 1

    sup = None
    loss_log = None
    if coord_dir:
        # per-rank loss log (jsonl, append-only): replayed steps after a
        # restart append AGAIN, so readers take the LAST occurrence of a
        # step — exactly the value an uninterrupted run would have
        os.makedirs(os.path.join(coord_dir, "loss"), exist_ok=True)
        loss_log = os.path.join(coord_dir, "loss", f"rank_{rank:05d}.jsonl")
    if multi:
        # background beater: heartbeat = PROCESS liveness, so a rank
        # stuck in a long XLA compile is not falsely evicted while a
        # SIGKILLed one is detected within --hb-timeout
        hb = Heartbeat(
            os.path.join(coord_dir, "hb"), rank=rank,
            interval_s=args.hb_interval,
        )
        HeartbeatThread(hb).start()
        dist_compat.initialize(
            coord_dir, process_id=rank, num_processes=world,
            timeout_s=args.rejoin_timeout,
        )
        refuse_shared_chips(coord_dir, rank)
        sup = FleetSupervisor(coord_dir, world, timeout_s=args.hb_timeout)
    else:
        hb = Heartbeat(os.path.join(ckpt_dir, "hb"), rank=0) if ckpt_dir else None
    strag = StragglerSupervisor()
    restart_policy = RestartPolicy(max_restarts=3, backoff_s=0.1)
    history = []
    injected = {"done": False}

    def log_loss(step: int, loss: float) -> None:
        if loss_log:
            with open(loss_log, "a") as f:
                f.write(json.dumps({"step": step, "loss": loss}) + "\n")

    def attempt(attempt_idx: int):
        # Evicted stragglers stay out of the fleet across restarts. A
        # single-host run only beats rank 0 (which can never straggle —
        # it is its own baseline), but a multi-host attempt would size
        # its data split around the survivors here.
        if restart_policy.excluded_ranks:
            print(f"[train] resharding around ranks {restart_policy.excluded_ranks}")
        membership = None
        active = [rank]
        if multi:
            membership = sup.view.read()
            if rank not in membership.active:
                # we were evicted (crash, stall, ...) — file a rejoin
                # request and wait for the supervisor to re-admit us
                sup.request_rejoin(rank)
                print(f"[train] rank {rank} evicted; requesting rejoin")
                membership = sup.wait_active(
                    rank, timeout_s=args.rejoin_timeout
                )
            active = list(membership.active)
            print(
                f"[train] rank {rank} attempt {attempt_idx}: "
                f"epoch {membership.epoch} active={active}"
            )
        saver = None
        if ckpt_dir:
            saver = ckpt_lib.AsyncCheckpointer(
                ckpt_dir,
                rank=rank,
                ranks=active if multi else None,
                commit_timeout_s=args.commit_timeout,
            )
        with jax.set_mesh(mesh):
            params, opt_state = init_train_state(cfg, mesh, args.seed)
            start = 0
            if ckpt_dir:
                latest = ckpt_lib.latest_step(ckpt_dir)
                if latest is not None:
                    state = ckpt_lib.restore(
                        ckpt_dir, latest,
                        {"params": params, "m": opt_state.m, "v": opt_state.v},
                        shardings={"params": p_sh, "m": opt_sh, "v": opt_sh},
                    )
                    params = state["params"]
                    opt_state = adam.AdamState(
                        jnp.asarray(latest, jnp.int32), state["m"], state["v"]
                    )
                    start = latest
                    print(f"[train] resumed from step {latest}")

            for step in range(start, args.steps):
                if multi:
                    if sup.should_poll(rank):
                        sup.poll()
                    # abort + reshard if the fleet changed under us
                    membership = sup.check_epoch(membership.epoch)
                if step == args.fail_at_step and not injected["done"]:
                    injected["done"] = True
                    raise RuntimeError("injected failure (fault-tolerance test)")
                if args.step_delay > 0:
                    time.sleep(args.step_delay)
                fn = trainer.step_for(step)
                rate = program.schedule.rate(step)
                batch = jax.tree.map(jnp.asarray, pipe.batch_at(step))
                t0 = time.time()
                params, opt_state, metrics = fn(params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                strag.record(rank, dt)
                strag.check(excluded=restart_policy.excluded_ranks)
                if hb:
                    hb.beat()
                history.append(loss)
                log_loss(step, loss)
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(
                        f"[train] step {step:5d} rate={rate:.2f} "
                        f"loss={loss:.4f} ({dt*1e3:.0f} ms)"
                    )
                if saver and (step + 1) % args.ckpt_every == 0:
                    saver.save(
                        step + 1,
                        {"params": params, "m": opt_state.m, "v": opt_state.v},
                    )
            if saver:
                saver.wait()
                if saver.last_error is not None:
                    # a failed FINAL save must not report success — mid-run
                    # save errors (e.g. a torn commit after a peer died)
                    # surface on the next attempt's restore instead
                    raise saver.last_error
        return {"history": history, "final_loss": history[-1] if history else None}

    out = restart_policy.run(
        attempt,
        on_restart=lambda i, e: print(f"[train] restart {i}: {e}"),
        on_evict=lambda r, e: print(f"[train] evicted straggler rank {r}: {e}"),
        on_reshard=lambda m: print(
            f"[train] rank {rank} resharding to epoch {m.epoch} "
            f"active={list(m.active)}"
        ),
    )
    if coord_dir:
        # durable completion marker for the multi-process harness
        os.makedirs(os.path.join(coord_dir, "done"), exist_ok=True)
        done = os.path.join(coord_dir, "done", f"rank_{rank:05d}.json")
        tmp = f"{done}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"rank": rank, "final_loss": out["final_loss"],
                       "steps": args.steps}, f)
        os.replace(tmp, done)
    return out


def main():
    args = build_parser().parse_args()
    compile_cache.enable()
    out = run(args)
    if out["final_loss"] is None:
        print("[train] nothing to do: already at the target step")
    else:
        print(f"[train] done. final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
