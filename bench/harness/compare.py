"""The comparison that decides ``correct`` for a training cell.

Numbers that ``bench/limits/<workload>.json`` may name, each with its limit:

* ``loss_gap``: over the first steps, the largest ``|L - L_ref| / |L_ref|``;
* ``loss_gap_first``: the first step's loss gap alone;
* ``grad_gap``: at the first step, the worst leaf's
  ``|n - n_ref| / max(n_ref, median leaf n_ref)``, ``n`` the norm of that
  leaf's gradient as the optimizer got it;
* ``change_gap``: the same for each leaf's change over the first steps;
* ``grad_gap_median`` / ``change_gap_median``: the median leaf's gap;
* ``head_grad_diff``: the worst classifier-head leaf's
  ``|g - g_ref| / max(|g_ref|, median head leaf |g_ref|)`` at the first
  step. The head's gradient is made of forward quantities alone (pooled
  features and softmax), so it carries the forward pass's rounding at
  first order and none of the backward's amplification;
* ``selection_gap``: over the first steps and every conv site that drops
  blocks, how far the least important block that the run kept falls
  below the reference's last kept one, ``(i_k - min i_kept) / i_k`` in the
  reference's importance ``i`` (0 where the run kept the reference's
  blocks, 1 where it kept another number of blocks). The reference keeps
  the run's blocks where their number is right, so that blocks tied to
  rounding do not part the two; this number holds the choice itself.

A leaf whose reference gradient is under a thousandth of the median
leaf's (BatchNorm's running statistics, which the step never reads) moves
by round-off alone and is left out of both norm gaps.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

QUIET_LEAF = 1e-3
# a conv's block of weight-gradient rows counts as kept above this share
# of the site's largest block norm (a dropped block's rows are zero)
SELECT_FLOOR = 1e-4


def leaf_gaps(prog: dict, ref: dict, counted: list[str]) -> dict[str, float]:
    """Each counted leaf's gap, against the larger of its own reference
    norm and the median counted leaf's."""
    if set(prog) != set(ref):
        return {k: math.inf for k in counted}
    median = statistics.median(ref[k] for k in counted)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median) for k in counted}


def counted_leaves(ref: dict) -> list[str]:
    rg = ref["grad_norms"]
    median = statistics.median(rg.values())
    return sorted(k for k, v in rg.items() if v >= QUIET_LEAF * median)


def kept_mask(norms) -> np.ndarray:
    n = np.asarray(norms, np.float64)
    return n > SELECT_FLOOR * n.max()


def picks(kept: list[dict]) -> list[dict]:
    """Each step's ``{site: [nb] 0/1}`` blocks that a run kept: what the
    reference follows."""
    return [{k: kept_mask(v).astype(np.float32) for k, v in step.items()} for step in kept]


def selection_gap(prog: dict, ref: dict) -> float:
    if len(prog["kept"]) != len(ref["importance"]):
        return math.inf
    worst = 0.0
    for kept, imp in zip(prog["kept"], ref["importance"], strict=True):
        for site, kb in ref["keep_blocks"].items():
            i = np.asarray(imp[site], np.float64)
            if kb >= i.size:
                continue
            mask = kept_mask(kept[site])
            if mask.sum() != kb:
                return 1.0
            last = np.sort(i)[::-1][kb - 1]
            worst = max(worst, (last - i[mask].min()) / last)
    return worst


def _loss_gap(prog, ref):
    if len(prog["losses"]) != len(ref["losses"]):
        return math.inf
    return max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"], strict=True))


def _head_grad_diff(prog, ref):
    p, r = prog["head_grads"], ref["head_grads"]
    if set(p) != set(r):
        return math.inf
    norms = {k: float(np.linalg.norm(r[k])) for k in r}
    median = statistics.median(norms.values())
    return max(
        float(np.linalg.norm(np.asarray(p[k], np.float64) - r[k])) / max(norms[k], median)
        for k in r
    )


def _leaf(key, pick):
    def gap(prog, ref):
        per_leaf = list(leaf_gaps(prog[key], ref[key], counted_leaves(ref)).values())
        return pick(per_leaf) if per_leaf else math.inf

    return gap


NUMBERS = {
    "loss_gap": _loss_gap,
    "loss_gap_first": lambda p, r: abs(p["losses"][0] - r["losses"][0]) / abs(r["losses"][0]),
    "grad_gap": _leaf("grad_norms", max),
    "grad_gap_median": _leaf("grad_norms", statistics.median),
    "change_gap": _leaf("change_norms", max),
    "change_gap_median": _leaf("change_norms", statistics.median),
    "head_grad_diff": _head_grad_diff,
    "selection_gap": selection_gap,
}


def gaps(prog: dict, ref: dict, names=None) -> dict[str, float]:
    """The numbers ``names`` (every one by default) of a run against the
    reference."""
    out = {n: NUMBERS[n](prog, ref) for n in (NUMBERS if names is None else names)}
    # NaN compares false against any limit; make it fail loudly instead
    return {k: (math.inf if math.isnan(v) else v) for k, v in out.items()}


def checks(values: dict[str, float], limits: dict) -> dict[str, dict]:
    """``{name: {"value", "limit"}}`` for every number the cell's limits
    name; a cell compares only those."""
    return {n: {"value": values[n], "limit": lim} for n, lim in limits.items()}


def passed(checked: dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
