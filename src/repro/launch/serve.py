"""LM serving CLI — thin front-end over the continuous-batching engine.

The serving loop itself lives in :mod:`repro.serve`: a slot-based
request scheduler with chunked prefill (requests join and leave the
batch mid-flight). ``--engine paged`` switches the KV cache to the
paged/block layout (``--block-size`` tokens per page, ``--n-blocks``
pool size — 0 sizes the pool to contiguous parity); ``--engine
lockstep`` runs the static lock-step baseline instead (every request
arrives together, the whole batch stalls until the longest generation
finishes) — kept for A/B comparison and as the parity oracle.

Sampling: ``--temperature`` > 0 samples every request (with
``--top-k``/``--top-p``) under per-request seeds derived from
``--seed``; the default 0 keeps greedy argmax. ``--preempt
swap|recompute|auto`` picks the pool-exhaustion policy (paged engine);
sampled requests require swap (auto does the right thing). ``--stream``
prints each token event as it is emitted instead of only the final
summary.

Speculative decoding: ``--spec-k k`` has a drafter propose k tokens per
decode slot which the target verifies in one chunk — the token stream
is bit-identical, only the step count drops. ``--draft-layers n`` builds
a depth-reduced drafter from the same architecture (0, the default,
self-drafts with the target — every proposal accepted; useful as a
sanity check, not a speedup, since the drafter is as expensive as the
target).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --reduced --batch 4 --prompt-len 16 --gen 32 --arrival-rate 0.5 \
      --temperature 0.8 --top-p 0.95 --stream
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.dist import sharding as shd
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as lm
from repro.serve import (
    ContinuousBatchingEngine,
    ServeConfig,
    generate_lockstep,
    lockstep_waves,
    poisson_workload,
)


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="slot capacity B")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to serve (default: one per slot)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals per engine tick (0 = all at t=0)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=0)
    ap.add_argument("--engine", choices=("paged", "continuous", "lockstep"),
                    default="continuous")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page (paged engine)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="page-pool size (0 = contiguous-parity pool)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass (1.0 = off)")
    ap.add_argument("--preempt", choices=("auto", "swap", "recompute"),
                    default="auto",
                    help="pool-exhaustion policy (paged engine)")
    ap.add_argument("--attn-kernel", action="store_true",
                    help="Pallas paged-attention kernel: read K/V pages "
                    "in place via the block table (paged engine only)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed per "
                    "decode slot per step (0 = off)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="drafter depth for speculative decoding (0 = "
                    "self-draft with the target model)")
    ap.add_argument("--stream", action="store_true",
                    help="print token events as they are emitted")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    return ap


def run(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.data_mesh, args.model_mesh)
    rng = jax.random.PRNGKey(args.seed)
    n_requests = args.requests or args.batch
    max_seq = args.prompt_len + args.gen + (cfg.n_patches or 0)

    with jax.set_mesh(mesh):
        # built on the device, placed as it is served: eager init is an
        # op-by-op dispatch over every leaf at published widths
        a_params = jax.eval_shape(lambda r: lm.init_params(cfg, r), rng)
        params = jax.jit(
            lambda r: lm.init_params(cfg, r),
            out_shardings=shd.param_shardings(mesh, a_params),
        )(rng)
        reqs = poisson_workload(
            cfg,
            n_requests=n_requests,
            arrival_rate=args.arrival_rate or 1e9,  # 0 -> everything at t=0
            prompt_len=args.prompt_len,
            gen_len=args.gen,
            seed=args.seed,
            uniform_prompts=True,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
        )

        if args.engine == "lockstep":
            # equal capacity with the continuous engine: static waves of
            # --batch requests in arrival order, each stalling on its
            # longest generation.
            steps = gen_tokens = 0
            prefill_s = decode_s = 0.0
            tokens_by_rid = {}
            for wave in lockstep_waves(reqs, args.batch):
                out = generate_lockstep(
                    cfg, params,
                    np.stack([r.prompt for r in wave]),
                    [r.max_new_tokens for r in wave],
                    max_seq=max_seq,
                    frames=np.stack([r.frames for r in wave])
                    if cfg.family == "encdec"
                    else None,
                    sampling=[r.sampling for r in wave],
                )
                steps += out["steps"]
                gen_tokens += out["generated_tokens"]
                prefill_s += out["prefill_s"]
                decode_s += out["decode_s"]
                for r, toks in zip(wave, out["tokens"], strict=True):
                    tokens_by_rid[r.rid] = toks
            gen = np.stack([tokens_by_rid[r.rid] for r in reqs])
            return {
                "generated": gen,
                "steps": steps,
                "prefill_s": prefill_s,
                "decode_s": decode_s,
                "tokens_per_s": gen_tokens / max(prefill_s + decode_s, 1e-9),
                "slot_utilization": 1.0,
            }

        paged = args.engine == "paged"
        draft_cfg = draft_params = None
        if args.spec_k and args.draft_layers:
            draft_cfg = cfg.reduced(n_layers=args.draft_layers)
            draft_params = lm.init_params(draft_cfg, jax.random.PRNGKey(args.seed + 1))
        engine = ContinuousBatchingEngine(
            cfg,
            params,
            ServeConfig(
                max_slots=args.batch,
                max_seq=max_seq,
                prefill_chunk=args.prefill_chunk,
                token_budget=args.token_budget,
                block_size=args.block_size if paged else 0,
                n_blocks=args.n_blocks if paged else 0,
                attn_kernel=args.attn_kernel,
                preempt=args.preempt,
                spec_k=args.spec_k,
            ),
            mesh=mesh,
            draft_cfg=draft_cfg,
            draft_params=draft_params,
        )
        for r in reqs:
            engine.submit(r)
        on_token = None
        if args.stream:
            def on_token(ev):
                tail = " <eos>" if ev.is_last else ""
                print(f"[stream] rid={ev.rid} token={ev.token}{tail}")
        results = engine.run(on_token=on_token)
        stats = engine.stats()

    gen = np.stack([results[r.rid] for r in reqs])
    return {
        "generated": gen,
        "steps": stats["compute_steps"],
        "prefill_s": stats["prefill_s"],
        "decode_s": stats["decode_s"],
        "tokens_per_s": stats["generated_tokens"]
        / max(stats["prefill_s"] + stats["decode_s"], 1e-9),
        "tokens_per_step": stats["tokens_per_step"],
        "slot_utilization": stats["slot_utilization"],
        "peak_concurrency": stats["peak_concurrency"],
        "preemptions": stats["preemptions"],
        "swap_preemptions": stats["swap_preemptions"],
        "recompute_preemptions": stats["recompute_preemptions"],
        "spec_proposed": stats["spec_proposed"],
        "spec_accepted": stats["spec_accepted"],
        "acceptance_rate": stats["acceptance_rate"],
        "draft_steps": stats["draft_steps"],
    }


def main():
    args = build_parser().parse_args()
    compile_cache.enable()
    out = run(args)
    print(f"[serve] engine={args.engine} slots={args.batch} gen={args.gen} "
          f"steps={out['steps']}")
    print(f"[serve] prefill {out['prefill_s']*1e3:.0f} ms, decode {out['decode_s']*1e3:.0f} ms"
          f" ({out['tokens_per_s']:.1f} tok/s, "
          f"slot util {out['slot_utilization']*100:.0f}%)")
    if "preemptions" in out:
        print(f"[serve] peak concurrency {out['peak_concurrency']}, "
              f"preemptions {out['preemptions']} "
              f"(swap {out['swap_preemptions']}, "
              f"recompute {out['recompute_preemptions']})")
    if args.spec_k and "spec_proposed" in out:
        print(f"[serve] speculative: accepted {out['spec_accepted']}"
              f"/{out['spec_proposed']} draft tokens "
              f"({out['acceptance_rate']*100:.0f}%), "
              f"{out['draft_steps']} draft steps")
    print("[serve] first request tokens:", out["generated"][0][:16].tolist())


if __name__ == "__main__":
    main()
