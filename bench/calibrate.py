"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--kinds control,half_batch] [--out <file.jsonl>]

On the chip, at the cell's own sizes, in one process: for every seed the
program's first steps (the window's compiled step and feed) against the
plain reference, and for every control seed the reference put in the
program's place, each held against the reference that follows its
blocks: the control (every contraction's operands in float8), the same
step in bfloat16, and the planted faults (the loss over half the batch, a
doubled weight gradient, and, where blocks are dropped, the least
important blocks kept). Prints one JSON line per reading, the compiled
step's memory analysis beside the device's memory statistics, and a
summary of the largest sound reading and the smallest reading of each
other kind for every number. A state left unchanged reads 1 on
``change_gap`` and ``grad_gap`` by construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as bench_run  # bench/run.py, beside this file

bench_run.setup_paths()

from bench.drivers import train_classifier as tc  # noqa: E402
from bench.harness import cell as cell_lib  # noqa: E402
from bench.harness import compare, device  # noqa: E402


def _emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.flush()


def _memory(prog, devs) -> dict:
    m = prog.step.memory_analysis()
    rec = {k: getattr(m, k) for k in dir(m) if k.endswith("_in_bytes")}
    rec["memory_stats"] = devs[0].memory_stats()
    return rec


def calibrate(
    root, workload, seeds, control_seeds, out=None, require_accelerator=True, only=None
):
    cell = cell_lib.resolve(root, workload)
    devs = device.devices(cell.chips, require_accelerator=require_accelerator)
    ref = cell_lib.reference_module(cell)
    cfg, mix = cell.config, cell.mix
    sparsity = (mix["granularity"], mix["block_size"], float(mix["drop_rate"]))
    kinds = {
        "control": ("fp8", ""),
        "bfloat16": ("bfloat16", ""),
        "half_batch": ("float32", "half_batch"),
        "dw_x2": ("float32", "dw_x2"),
    }
    if sparsity[2] > 0:
        kinds["wrong_block"] = ("float32", "wrong_block")
    if only:
        kinds = {k: v for k, v in kinds.items() if k in only}
    steps = {k: ref.make_step(cfg, sparsity, p, f) for k, (p, f) in kinds.items()}
    reference = ref.make_step(cfg, sparsity)
    summary = {}
    step = None
    for seed in seeds:
        t = time.perf_counter()
        prog, prog_r = tc.build(cell, seed, step)
        if step is None and require_accelerator:
            _emit(out, {"workload": workload, "kind": "memory", **_memory(prog, devs)})
        step = prog.step
        del prog
        gc.collect()
        t_prog = time.perf_counter() - t
        runs = {"program": prog_r}
        if seed in control_seeds:
            for kind in kinds:
                runs[kind] = tc.reference_readings(cell, seed, step=steps[kind])
        for kind, r in runs.items():
            t = time.perf_counter()
            ref_r = tc.reference_readings(
                cell, seed, step=reference, follow=compare.picks(r["kept"])
            )
            g = compare.gaps(r, ref_r)
            rec = {"workload": workload, "seed": seed, "kind": kind, **g,
                   "reference_s": time.perf_counter() - t,
                   "losses": r["losses"], "ref_losses": ref_r["losses"]}
            counted = compare.counted_leaves(ref_r)
            for key in ("grad_norms", "change_norms"):
                lg = compare.leaf_gaps(r[key], ref_r[key], counted)
                worst = sorted(lg, key=lg.get, reverse=True)[:3]
                rec[f"worst_{key}"] = [[k, lg[k], r[key][k], ref_r[key][k]] for k in worst]
            if kind == "program":
                rec["program_s"] = t_prog
            _emit(out, rec)
            agg = summary.setdefault(kind, {})
            for name, v in g.items():
                pick = max if kind == "program" else min
                agg[name] = pick(agg.get(name, v), v)
    _emit(out, {"workload": workload, "summary": summary})
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--kinds", default="", help="comma-separated subset of the stand-ins")
    args = ap.parse_args(argv)
    bench_run.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    only = {k for k in args.kinds.split(",") if k}
    if not args.out:
        calibrate(bench_run.ROOT, args.workload, seeds, control, only=only)
        return 0
    with open(args.out, "a") as out:
        calibrate(bench_run.ROOT, args.workload, seeds, control, out, only=only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
