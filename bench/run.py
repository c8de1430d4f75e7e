"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for. The last lines of
standard error, and the ``checks`` key that ends the result line, give
each number of the correctness comparison beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / ".bench_out"
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass
class Context:
    cell: object
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    t_start: float
    peaks: object
    devices: list

    def memory_peak(self) -> int:
        from bench.harness import device

        return device.memory_peak(self.devices)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths() -> None:
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def enable_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(args, *, root=ROOT, require_accelerator=True, t_start=None):
    """Run the cell; return the result object (not printed)."""
    from bench.harness import cell as cell_lib
    from bench.harness import device, peaks

    cell = cell_lib.resolve(root, args.workload)
    devs = device.devices(cell.chips, require_accelerator=require_accelerator)
    info = device.describe(devs)
    ctx = Context(
        cell=cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        out_dir=str(OUT_DIR / cell.name),
        t_start=T_START if t_start is None else t_start,
        peaks=peaks.for_kind(info["kind"]) if require_accelerator else None,
        devices=devs,
    )
    os.makedirs(ctx.out_dir, exist_ok=True)
    out = cell.driver.run(ctx)
    units = {m.name: m.unit for m in (*cell.end_to_end, *cell.per_layer)}
    info["memory_peak_bytes"] = out["memory_peak_bytes"]
    if ctx.trace:
        info["busy_s"] = out["busy_s"]
        info["window_s"] = out["window_s"]
    result = {
        "correct": bool(out["correct"]),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {
            k: {"value": v, "unit": units[k]}
            for k, v in out["metrics"].items()
            if v is not None and math.isfinite(v)
        },
        "device": info,
    }
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["checks"] = {
        k: {"value": _finite(c["value"]), "limit": c["limit"]} for k, c in out["checks"].items()
    }
    return result


def _finite(v: float):
    """JSON has no infinity or NaN: such a value is printed as a string."""
    return v if math.isfinite(v) else str(v)


def main(argv=None) -> int:
    args = parse(argv)
    setup_paths()
    from bench.harness import device

    enable_cache()
    try:
        result = execute(args)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
