"""Split a traced run's device time by phase, conv site and ssProp stage.

    python bench/attribute.py --workload <cell> --seed <n> --seconds <s>

from the root of a checkout, on the chip. Runs the cell once as
``bench/run.py --trace 1`` does, keeping the compiled step that its
window ran, then reads its trace: every device operation's time is split
over the scopes of the work its instruction runs, as the step's HLO text
names it (``harness.phases``). Writes ``phases.json`` beside
``steps.json`` in the run's output directory (milliseconds per traced
step: each phase, the selection, the bounds of each, the time of
operations that mix scopes, each conv site's forward, selection,
contraction and other backward time, and the attributed share) and
prints one JSON line: the run's result line, the phase metrics as the
readers of ``bench/metrics/`` read them, the sparse-backward kernels'
milliseconds per step, the five sites with the most backward time, and
the images per second of the window's traced steps beside those of its
untraced rest (the cost of tracing). Goes once ``train_classifier.run``
writes ``phases.json`` itself.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import re
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402

bench_run.setup_paths()

from bench.drivers import train_classifier as tc  # noqa: E402
from bench.harness import cell as cell_lib  # noqa: E402
from bench.harness import phases, trace  # noqa: E402

METRICS = ("step_fwd_ms", "step_bwd_ms", "step_opt_ms", "sparse_select_share")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ", re.M)


def execute(args) -> tuple[dict, object]:
    """Run the cell as ``bench/run.py`` does; return its result and the
    compiled step that its window ran (``train_classifier.build``'s)."""
    kept = []
    build = tc.build

    def keep(*a, **kw):
        prog, readings = build(*a, **kw)
        kept.append(prog.step)
        return prog, readings

    tc.build = keep
    try:
        result = bench_run.execute(args)
    finally:
        tc.build = build
    return result, kept[-1]


def conv_sites(cell: cell_lib.Cell) -> tuple[str, ...]:
    from repro.models import resnet

    return resnet.site_names(cell.config["model"])[0]


def read_metrics(ph: phases.Phases | None) -> dict:
    obs = types.SimpleNamespace(phases=ph)
    return {m: importlib.import_module(f"bench.metrics.{m}").read(obs) for m in METRICS}


def attribute(cell: cell_lib.Cell, trace_dir, hlo_text: str) -> dict:
    """The phase split of the run whose trace is under ``trace_dir``:
    ``phases.json``'s content, the kernels' time and the metrics."""
    tr = trace.load(str(trace_dir))
    # the profiler stops between steps, so every step span it holds is whole
    steps = sum(s.name == trace.STEP_SPAN for s in tr.spans)
    sites = conv_sites(cell)
    ph = phases.reduce(tr, steps, phases.work(hlo_text), sites)
    red = trace.reduce(tr, steps, tc.SPARSE_BWD_KERNELS)
    known = set(_NAME.findall(hlo_text))
    lo, hi = trace.window(tr.spans, steps)
    unknown_s = sum(
        min(e.end, hi) - max(e.start, lo)
        for ops in tr.devices.values()
        for e in ops
        if e.end > lo and e.start < hi and e.name not in known
    )
    table = ph.per_step_ms()
    table["images_per_s"] = images_per_s(
        pathlib.Path(trace_dir).parent / "steps.json", steps, cell.config["batch"]
    )
    table["unknown_share"] = unknown_s / ph.op_s if ph.op_s > 0 else 0.0
    table["kernel_ms"] = 1e3 * red.kernel_s / steps
    bwd = lambda t: t["select"] + t["contract"] + t["backward_other"]  # noqa: E731
    top = sorted(table["site_ms"].items(), key=lambda kv: -bwd(kv[1]))[:5]
    return {
        "table": table,
        "metrics": read_metrics(ph),
        "top_sites_bwd_ms": [[s, bwd(t)] for s, t in top],
    }


def images_per_s(steps_json, traced: int, batch: int) -> dict:
    """Images per second over the traced steps and over the untraced
    rest of the window, leaving out the step in which the profiler
    stopped."""
    with open(steps_json) as f:
        run = json.load(f)
    t0, ends = run["t0"], run["ends"]
    out = {"traced": traced * batch / (ends[traced - 1] - t0) if traced else None}
    rest = len(ends) - traced - 1
    out["untraced"] = rest * batch / (ends[-1] - ends[traced]) if rest > 0 else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    args.trace = 1
    bench_run.enable_cache()
    result, step = execute(args)
    cell = cell_lib.resolve(ROOT, args.workload)
    out_dir = bench_run.OUT_DIR / cell.name
    split = attribute(cell, out_dir / "trace", step.as_text())
    with open(out_dir / "phases.json", "w") as f:
        json.dump(split["table"], f, indent=1)
    table = split["table"]
    print(json.dumps({
        "workload": cell.name,
        "seed": args.seed,
        "result": result,
        "phase_metrics": split["metrics"],
        "phase_ms": table["phase_ms"],
        "op_ms": table["op_ms"],
        "kernel_ms": table["kernel_ms"],
        "select_ms": table["select_ms"],
        "bounds_ms": table["bounds_ms"],
        "mixed_ms": table["mixed_ms"],
        "attributed_share": table["attributed_share"],
        "unknown_share": table["unknown_share"],
        "images_per_s": table["images_per_s"],
        "unattributed_ms": table["unattributed_ms"][:5],
        "top_sites_bwd_ms": split["top_sites_bwd_ms"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
