"""Required work and trace readings of a decoder LM's training step.

* :func:`step_flops`: the matmul FLOPs one step requires (a multiply-add
  is 2): every projection, the causal half of attention's two products
  and the tied head forward; then dX and dW of every projection at the
  channels its selection keeps, attention's backward (twice its forward)
  and the head's dX and dW. Recomputed work and elementwise work do not
  count.
* :func:`collective_share`: per chip, device time in collective
  operations over the union of its busy intervals, inside the window;
  the mean over the chips.
* :func:`scope_seconds`: device seconds of the work under each of a set
  of ``jax.named_scope`` names, each operation's time split over the
  instructions it runs as ``harness.phases`` splits it.
* :func:`leaf_ops`: the trace without the events of loops and branches,
  which span the operations of their bodies (the scanned layers), so
  that sums over operations count each once.

Everything works on plain dicts and ``trace.Trace`` tuples, so it can be
checked on the CPU.
"""
from __future__ import annotations

import collections
import re

from bench.harness import trace

_PATH = re.compile(r"[/()]")
_CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?$")
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all",
)


def kept_channels(c: int, spec: tuple[int, int, int]) -> int:
    """Output channels a projection's backward contracts, from its
    selection ``(shards, block, kept blocks per shard)``."""
    n, bs, keep = spec
    return min(c, n * keep * bs)


def step_flops(config: dict, batch: int, seq: int, sel: dict) -> int:
    """Required matmul FLOPs of one training step of the decoder in
    ``config`` (published key names) over ``batch`` sequences of ``seq``
    tokens, each projection's backward at the channels that ``sel``
    (``{projection: (shards, block, kept blocks per shard)}``) keeps."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // h
    shapes = {
        "q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd), "o": (h * hd, d),
        "gate": (d, ff), "up": (d, ff), "down": (ff, d),
    }
    t = batch * seq
    fwd = bwd = 0
    for name, (d_in, c) in shapes.items():
        fwd += 2 * t * d_in * c
        bwd += 2 * 2 * t * d_in * kept_channels(c, sel[name])  # dX and dW
    attn = 2 * 2 * batch * h * hd * (seq * (seq + 1) // 2)  # QK^T and PV, causal
    per_layer = fwd + bwd + 3 * attn
    head = 2 * t * d * config["vocab_size"]
    return config["num_hidden_layers"] * per_layer + 3 * head


def leaf_ops(tr: trace.Trace) -> trace.Trace:
    """``tr`` without the events of ``while``, ``conditional`` and ``call``
    operations: the trace also holds every operation of their bodies."""
    return trace.Trace(
        {d: [e for e in ops if not _CONTAINER.match(e.op or e.name)] for d, ops in tr.devices.items()},
        tr.spans,
    )


def is_collective(ev: trace.Event) -> bool:
    op = ev.op or trace.parse_op(ev.name)[1]
    return op.startswith(COLLECTIVES)


def collective_share(tr: trace.Trace, steps: int) -> float | None:
    """Mean over the chips of collective seconds over busy seconds in the
    window of ``steps`` steps; None where no chip was busy."""
    lo, hi = trace.window(tr.spans, steps)
    shares = []
    for ops in tr.devices.values():
        inside = [e for e in ops if e.end > lo and e.start < hi]
        busy = trace.busy_seconds(inside, lo, hi)
        if busy <= 0:
            continue
        coll = sum(
            min(e.end, hi) - max(e.start, lo) for e in inside if is_collective(e)
        )
        shares.append(coll / busy)
    return sum(shares) / len(shares) if shares else None


def scope_seconds(tr: trace.Trace, steps: int, runs: dict, scopes) -> dict[str, float]:
    """``{scope: device seconds}`` in the window, summed over the chips:
    each operation's time split over the work it runs (``runs``, from
    ``phases.work``) by weight, the share whose ``op_name`` has a path
    component equal to the scope counted to it."""
    lo, hi = trace.window(tr.spans, steps)
    out: dict[str, float] = collections.defaultdict(float)
    seen: dict[str, dict[str, float]] = {}
    for ops in tr.devices.values():
        for e in ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d <= 0:
                continue
            if e.name not in seen:
                work = runs.get(e.name, ())
                total = sum(w for _, w in work)
                share = dict.fromkeys(scopes, 0.0)
                for name, w in work:
                    parts = set(_PATH.split(name))
                    for s in scopes:
                        if s in parts and total > 0:
                            share[s] += w / total
                seen[e.name] = share
            for s, f in seen[e.name].items():
                out[s] += d * f
    return {s: out[s] for s in scopes}
