"""Reduce a profiler trace of the measured window to device metrics.

The benchmark's own loop writes host spans into the profiler's trace
(``jax.profiler.TraceAnnotation``): ``bench/step`` around each step, and
inside it ``bench/batch_transfer``, ``bench/dispatch`` and
``bench/loss_read``. Device operations are the events of the ``XLA Ops``
line of every ``/device:`` plane. From those:

* busy seconds: the union of the device operations' intervals inside the
  window, averaged over the chips that ran any;
* idle gaps: the stretches of the window in which no operation ran,
  each named after the host span that overlaps it most;
* kernel seconds: the summed durations of the custom calls whose
  instruction name holds one of a set of kernel names (XLA names a
  Pallas call after the jitted wrapper around it, e.g.
  ``transpose_jvp_jit_conv_dx_fused___.32``);
* the operations that took the most device time.

Everything below :func:`load` works on plain tuples, so it can be checked
on a hand-built trace.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench/"
STEP_SPAN = "bench/step"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str  # the HLO instruction's name for a device operation
    start: float  # seconds on the trace's clock
    end: float
    op: str = ""  # the HLO opcode ("fusion", "custom-call", ...)


# A device operation's event is named by its HLO text:
# "%name = <shape> opcode(operands), ...". The shape may be a tuple.
_HLO = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9_-]*)\(")


def parse_op(text: str) -> tuple[str, str]:
    """``(instruction name, opcode)`` of a device operation's event name."""
    m = _HLO.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


@dataclasses.dataclass(frozen=True)
class Trace:
    devices: dict  # plane name -> list[Event] of device operations
    spans: list  # host spans of the benchmark's own loop


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    start = e.start_ns * 1e-9
                    name, op = parse_op(e.name)
                    ops.append(Event(name, start, start + e.duration_ns * 1e-9, op))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = e.start_ns * 1e-9
                        spans.append(Event(e.name, start, start + e.duration_ns * 1e-9))
    return Trace(devices, spans)


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the events' intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(ev.start, lo), min(ev.end, hi)) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    gaps, cur = [], lo
    for s, e in merged(events, lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def attribute(gap: tuple[float, float], spans) -> str:
    """The innermost-named host span overlapping ``gap`` the most."""
    best, best_overlap = "none", 0.0
    for sp in spans:
        if sp.name == STEP_SPAN:
            continue
        ov = min(gap[1], sp.end) - max(gap[0], sp.start)
        if ov > best_overlap:
            best, best_overlap = sp.name[len(SPAN_PREFIX):], ov
    return best


def window(spans, steps: int) -> tuple[float, float]:
    """From the start of the first step span to the end of the ``steps``-th."""
    st = sorted((s for s in spans if s.name == STEP_SPAN), key=lambda s: s.start)
    if len(st) < steps or steps < 1:
        raise ValueError(f"trace holds {len(st)} step spans, the window {steps}")
    return st[0].start, st[steps - 1].end


def kernel_of(ev: Event, kernels) -> str | None:
    """The kernel a custom call belongs to, from the names its
    instruction carries (the jitted wrapper's name), or None."""
    if ev.op != "custom-call":
        return None
    return next((k for k in kernels if k in ev.name), None)


@dataclasses.dataclass(frozen=True)
class Reduction:
    window_s: float
    busy_s: float  # mean over the chips that ran operations
    kernel_s: float  # summed over the chips
    device_ops: list  # [[name, seconds]] most device time first
    idle_gaps: list  # [[host span, seconds]] longest first


def reduce(tr: Trace, steps: int, kernels=(), top: int = 10) -> Reduction:
    lo, hi = window(tr.spans, steps)
    busy, kernel_s = [], 0.0
    per_op: collections.Counter = collections.Counter()
    gaps: list[tuple[float, float, float]] = []
    for ops in tr.devices.values():
        inside = [e for e in ops if e.end > lo and e.start < hi]
        busy.append(busy_seconds(inside, lo, hi))
        for e in inside:
            d = min(e.end, hi) - max(e.start, lo)
            label = e.name
            kernel = kernel_of(e, kernels)
            if kernel:
                kernel_s += d
                label = f"{e.name} [{kernel}]"
            per_op[label] += d
        gaps.extend((e - s, s, e) for s, e in idle_gaps(inside, lo, hi))
    if not busy:
        raise ValueError("the trace holds no device operations")
    gaps.sort(reverse=True)
    named = [[attribute((s, e), tr.spans), d] for d, s, e in gaps[:top]]
    return Reduction(
        window_s=hi - lo,
        busy_s=sum(busy) / len(busy),
        kernel_s=kernel_s,
        device_ops=[[n, s] for n, s in per_op.most_common(top)],
        idle_gaps=named,
    )
