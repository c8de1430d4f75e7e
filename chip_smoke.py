"""Smoke run of ssProp's main paths on a TPU, through the normal entry points.

One process drives every phase (a chip belongs to one process at a time).
Informational lines start with ``[smoke]``; the last line of standard
output is one JSON object, printed only when every phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases on one chip (the default):

* **device** - exits non-zero, printing no result, unless JAX's devices
  are TPUs.
* **train** - ResNet-18 on the paper's CIFAR-10 task (3x32x32, 10
  classes, batch 128, Adam 2e-4; synthetic ``ImagePipeline`` data from
  ``--seed``), with the TPU policy (128-channel blocks, drop rate 0.8,
  ``use_pallas`` on) under the 2-epoch bar schedule cut to a short epoch,
  so the dense and the sparse executable both run. Checks finite losses,
  no ``tpu_custom_call`` in the sparse executable (every conv site that
  drops a block takes XLA's gathered conv), and on one batch the
  gathered-route gradients against the ``mask_mode`` oracle.
* **serve** - the Pallas paged-attention kernel against the engine's
  gather path at qwen2.5-3b heads (S=1 and S=8), then
  ``repro.launch.serve.run`` for qwen2.5-3b at published width (random
  bf16 weights from ``--seed``), paged engine, once with and once
  without the kernel: both must generate every token, and how far their
  greedy streams agree is reported.

``--four-chips`` runs only the sharded-training phase instead:
``repro.launch.train.run`` for qwen2.5-3b at full width on a 1x4
(data x model) mesh for a few steps, and the same widths cut to two
layers stepped once on a one-device mesh and once on the 1x4 mesh, whose
losses and updated-parameter checksums must agree.

Run from the repository root: ``python chip_smoke.py [--four-chips]``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper import CLASSIFICATION  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.policy import tpu_default  # noqa: E402
from repro.core.schedulers import make_schedule  # noqa: E402
from repro.data.pipeline import (  # noqa: E402
    ImagePipeline,
    ImagePipelineConfig,
    TokenPipeline,
    TokenPipelineConfig,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import compile_cache, serve, train  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models import model as lm  # noqa: E402
from repro.models import resnet  # noqa: E402
from repro.optim import adam  # noqa: E402

# Gathered-route gradients against the mask_mode oracle: max over parameter
# leaves of ||g_gather - g_mask|| / ||g_mask||, both computed at "highest"
# matmul precision. The two routes contract in different orders and tiles,
# so they agree to f32 rounding; a wrong or missing kept block moves a
# leaf by the block's whole share (tens of percent).
GRAD_RTOL = 1e-2
# paged-attention kernel vs the gather path: max |difference| over max
# |output|. Both accumulate in fp32 and round the output to bf16, whose
# spacing is 2^-8 (0.4%) of a value; a misaddressed page or a wrong mask
# moves whole rows.
ATTN_RTOL = 1e-2
# one-device vs 1x4-mesh step (bf16 weights, f32 accumulation): relative
# difference of the loss and of sum |p_new - p_init| over all parameters.
SHARD_RTOL = 1e-2
STEPS_PER_EPOCH = 3  # 2-epoch bar cut short: 3 dense steps, 3 at 0.8


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", flush=True)
    raise SystemExit(1)


def device_phase(want: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"[smoke] no TPU: JAX sees {d.platform} devices only", file=sys.stderr)
        raise SystemExit(2)
    log(f"device: kind={d.device_kind!r} count={len(devices)} (info)")
    if len(devices) < want:
        fail(f"this phase needs {want} chips, JAX sees {len(devices)}")
    if kops._interpret():
        fail("Pallas kernels would run in interpret mode")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def train_phase(seed: int) -> None:
    task = CLASSIFICATION[("cifar10", "resnet18")]
    pipe = ImagePipeline(
        ImagePipelineConfig(task.image, task.n_classes, task.batch, seed=seed)
    )
    policy = dataclasses.replace(tpu_default(0.8), use_pallas=True)
    schedule = make_schedule(
        "epoch_bar", target=policy.target_rate,
        steps_per_epoch=STEPS_PER_EPOCH, rate_buckets=policy.rate_buckets,
    )
    opt_cfg = adam.AdamConfig(lr=task.lr)
    params = jax.jit(
        lambda r: resnet.init_params(task.model, r, task.n_classes)
    )(jax.random.PRNGKey(seed))
    opt = adam.init(params)

    executables = {}
    for step in range(2 * STEPS_PER_EPOCH):
        rate = schedule.rate(step)
        batch = pipe.batch_at(step)
        x, y = jnp.asarray(batch["images"]), jnp.asarray(batch["labels"])
        if rate not in executables:
            t0 = time.perf_counter()
            fn = jax.jit(steps_lib.make_classifier_step(
                task.model, policy.with_rate(rate), opt_cfg
            ))
            executables[rate] = fn.lower(params, opt, x, y).compile()
            log(f"train: compiled rate={rate} executable in "
                f"{time.perf_counter() - t0:.3f} s (info, set-up)")
        t0 = time.perf_counter()
        params, opt, loss = executables[rate](params, opt, x, y)
        loss = float(loss)
        log(f"train: step {step} rate={rate} loss={loss!r} "
            f"{(time.perf_counter() - t0) * 1e3:.3f} ms host wall (info)")
        if not math.isfinite(loss):
            fail(f"train: step {step} loss is {loss}")
    if set(executables) != {0.0, policy.target_rate}:
        fail(f"train: ran executables {sorted(executables)}, want dense and 0.8")
    n_custom = executables[policy.target_rate].as_text().count(
        'custom_call_target="tpu_custom_call"'
    )
    log(f"train: sparse executable holds {n_custom} tpu_custom_call ops")
    if n_custom != 0:
        fail(f"train: the sparse executable holds {n_custom} tpu_custom_call ops, want 0")

    # the gathered route vs the mask_mode oracle on one batch
    batch = pipe.batch_at(0)
    x, y = jnp.asarray(batch["images"]), jnp.asarray(batch["labels"])
    sparse = policy.with_rate(policy.target_rate)
    oracle = dataclasses.replace(sparse, mask_mode=True, use_pallas=False)

    def grads(pol):
        return jax.jit(jax.grad(
            lambda p: steps_lib.classifier_loss(task.model, pol, p, x, y)
        ))(params)

    with jax.default_matmul_precision("highest"):
        g_gather, g_mask = grads(sparse), grads(oracle)
    errs = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)),
        g_gather, g_mask,
    )
    worst = max(jax.tree.leaves(errs))
    log(f"train: gather vs mask gradients: max relative error {worst!r} "
        f"(tolerance {GRAD_RTOL})")
    if not worst <= GRAD_RTOL:
        fail(f"train: gathered gradients differ from the mask oracle by {worst}")


def attention_check(seed: int) -> None:
    """The paged-attention kernel against the engine's gather path (the
    page gather + ``masked_attention`` that ``attn_apply`` runs without
    the kernel) at qwen2.5-3b heads, on the engine's dtypes: bf16
    queries, an fp32 page pool holding bf16 values."""
    cfg = get_config("qwen2.5-3b")
    b, nb, bs = 4, 5, 16  # 4 slots, 80-token rows in 16-token pages
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = (b * nb, bs, cfg.n_kv_heads, cfg.head_dim)
    k_pool, v_pool = (
        jax.random.normal(key, pool).astype(jnp.bfloat16).astype(jnp.float32)
        for key in keys[:2]
    )
    tables = jax.random.permutation(keys[2], b * nb).reshape(b, nb).astype(jnp.int32)
    for s in (1, 8):  # a decode step and a prefill chunk
        q = jax.random.normal(
            keys[3], (b, s, cfg.n_heads, cfg.head_dim), jnp.bfloat16
        )
        qpos = jnp.asarray([[3], [17], [40], [nb * bs - s]]) + jnp.arange(s)
        out = kops.paged_attention(q, k_pool, v_pool, tables, qpos)
        gather = lambda pool: pool[tables].reshape(b, nb * bs, *pool.shape[2:])  # noqa: E731
        ref = layers.masked_attention(
            q, gather(k_pool), gather(v_pool), causal=True, qpos=qpos
        )
        err = float(
            jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()
            / jnp.abs(ref.astype(jnp.float32)).max()
        )
        log(f"serve: paged-attention kernel vs gather at S={s}: max error "
            f"{err!r} of max |out| (tolerance {ATTN_RTOL})")
        if not err <= ATTN_RTOL:
            fail(f"serve: paged-attention kernel differs from the gather path by {err}")


def serve_phase(seed: int) -> None:
    attention_check(seed)
    vocab = get_config("qwen2.5-3b").vocab
    argv = [
        "--arch", "qwen2.5-3b", "--engine", "paged", "--block-size", "16",
        "--batch", "4", "--requests", "8", "--prompt-len", "64", "--gen", "16",
        "--seed", str(seed),
    ]
    streams = {}
    for kernel in (False, True):
        args = serve.build_parser().parse_args(
            argv + (["--attn-kernel"] if kernel else [])
        )
        t0 = time.perf_counter()
        out = serve.run(args)
        gen = np.asarray(out["generated"])
        log(f"serve: attn_kernel={kernel} {gen.shape[0]} requests x "
            f"{gen.shape[1]} tokens in {time.perf_counter() - t0:.3f} s host "
            f"wall incl. compile; engine prefill {out['prefill_s']!r} s, "
            f"decode {out['decode_s']!r} s (info)")
        if gen.shape != (8, 16) or gen.min() < 0 or gen.max() >= vocab:
            fail(f"serve: generated {gen.shape} tokens in "
                 f"[{gen.min()}, {gen.max()}], want (8, 16) in [0, {vocab})")
        streams[kernel] = gen
        del out
        gc.collect()
    # Greedy streams match exactly in fp32 (tests/test_serve.py). At bf16
    # the kernel's online softmax rounds differently from the gather's
    # two-pass softmax, and a near-tied argmax may then pick another
    # token; from there the request's stream diverges. Informational.
    same = streams[False] == streams[True]
    prefix = [int(np.argmin(row)) if not row.all() else row.size for row in same]
    log(f"serve: kernel and gather streams agree on {int(same.sum())}/"
        f"{same.size} tokens; agreeing prefix per request {prefix} (info)")


def _two_layer_step(cfg, mesh, seed: int, table, opt_cfg, batch):
    """Loss and sum |p_new - p_init| of one train step on ``mesh``."""
    step = steps_lib.make_train_step(cfg, table, opt_cfg)

    def loss_and_checksum(params, opt_state, batch):
        new_params, _, metrics = step(params, opt_state, batch)
        moved = sum(
            jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(
                jax.tree.leaves(new_params), jax.tree.leaves(params), strict=True
            )
        )
        return metrics["loss"], moved

    with jax.set_mesh(mesh):
        params, opt_state = train.init_train_state(cfg, mesh, seed)
        loss, moved = jax.jit(loss_and_checksum)(params, opt_state, batch)
        return float(loss), float(moved)


def four_chip_phase(seed: int) -> None:
    argv = [
        "--arch", "qwen2.5-3b", "--granularity", "block",
        "--global-batch", "4", "--seq-len", "2048",
        "--steps", "3", "--steps-per-epoch", "1", "--log-every", "1",
        "--seed", str(seed),
    ]
    args = train.build_parser().parse_args(argv + ["--data-mesh", "1", "--model-mesh", "4"])

    # comparison first, on an empty device: one step at the sparse rate
    cfg2 = dataclasses.replace(get_config(args.arch), n_layers=2)
    sites, depth = lm.site_names(cfg2)
    program = train.build_program(args, tpu_default(args.drop_rate))
    table = program.resolve(sites, depth=depth).policies_for_step(1)
    opt_cfg = adam.AdamConfig(lr=args.lr, clip_norm=1.0, total_steps=args.steps)
    pipe = TokenPipeline(
        TokenPipelineConfig(cfg2.vocab, args.seq_len, args.global_batch, seed)
    )
    batch = jax.tree.map(jnp.asarray, pipe.batch_at(0))
    results = {}
    for data, model in ((1, 1), (1, 4)):
        t0 = time.perf_counter()
        results[model] = _two_layer_step(
            cfg2, make_host_mesh(data, model), seed, table, opt_cfg, batch
        )
        gc.collect()
        log(f"four-chip: 2-layer step on a {data}x{model} mesh: loss "
            f"{results[model][0]!r}, sum|dp| {results[model][1]!r} "
            f"({time.perf_counter() - t0:.3f} s host wall incl. compile, info)")
    d_loss = _rel(results[4][0], results[1][0])
    d_moved = _rel(results[4][1], results[1][1])
    log(f"four-chip: 1x4 vs 1x1 relative difference: loss {d_loss!r}, "
        f"sum|dp| {d_moved!r} (tolerance {SHARD_RTOL})")
    if not (d_loss <= SHARD_RTOL and d_moved <= SHARD_RTOL):
        fail("four-chip: the 1x4 mesh step disagrees with the one-device step")

    t0 = time.perf_counter()
    out = train.run(args)
    history = out["history"]
    log(f"four-chip: qwen2.5-3b 1x4 losses {history!r} "
        f"({time.perf_counter() - t0:.3f} s host wall incl. compile, info)")
    if len(history) != args.steps or not all(map(math.isfinite, history)):
        fail(f"four-chip: losses {history}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on four chips")
    args = ap.parse_args()
    device = device_phase(4 if args.four_chips else 1)
    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        train_phase(args.seed)
        serve_phase(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s (info)")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
