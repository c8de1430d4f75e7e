"""Recursive ClosedJaxpr walker: FLOPs bounds + program census.

The auditor's measurement layer. Walks a jaxpr the way XLA will run it
— recursing into ``pjit``/``scan``/``cond``/``while``/``remat``/custom-
vjp call bodies and into ``pallas_call`` kernel jaxprs — and produces:

  * **contraction FLOPs bounds** ``(flops_lo, flops_hi)``: every live
    ``dot_general`` / ``conv_general_dilated`` counted exactly; ``scan``
    bodies multiply by the trip count, ``pallas_call`` kernels by the
    grid size, and ``cond`` contributes ``min``/``max`` over its
    branches (a ``pl.when`` inside a kernel lowers to ``cond``, so
    masked grid steps naturally widen the interval instead of guessing).
  * **census** for the lint passes: per-contraction operand dtypes (the
    bf16-region leak check), ``convert_element_type`` records, host
    callback sightings, dead equations and the contraction FLOPs buried
    in them.

Liveness is computed per jaxpr by a reverse sweep from the live outputs
(an equation is live iff any output is demanded or it has effects), so
counting a backward-only program automatically excludes the dead forward
half that ``jax.vjp`` drags along — and the same sweep is the dead-code
lint.

Conv FLOPs convention (matches ``core/flops.py``'s analytic tables):
per spatial dim the MAC pair count is ``O_i * K_i`` — output size times
filter taps — except when ``lhs_dilation > 1`` (a strided conv's dX
VJP), where the real work is ``L_i * K_i`` over the *undilated* operand
rows; counting the dilated output would bill the inserted zeros as
MACs. Total MACs = ``batch * C_out * (C_in / feature_groups) * prod(pairs)``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

from jax.extend import core as jcore

#: primitives that move data or control to the host from inside a
#: jitted program — forbidden in audited train/serve steps.
_CALLBACK_PRIMS = frozenset(
    {"outside_call", "host_callback", "infeed", "outfeed"}
)

_CONTRACTIONS = frozenset({"dot_general", "conv_general_dilated"})


@dataclasses.dataclass(frozen=True)
class Contraction:
    """One live matmul/conv with its launch context."""

    prim: str
    operand_dtypes: tuple[str, ...]
    out_dtype: str
    flops: int          # single-execution cost
    mult: int           # grid/scan multiplier at this program point
    in_cond: bool       # under a cond branch (pl.when etc.)
    path: str


@dataclasses.dataclass(frozen=True)
class Convert:
    src: str
    dst: str
    path: str


@dataclasses.dataclass
class Counts:
    """Everything the walker measures about one program."""

    flops_lo: int = 0
    flops_hi: int = 0
    dead_flops: int = 0
    dead_eqns: int = 0
    unbounded_loops: int = 0
    contractions: list[Contraction] = dataclasses.field(default_factory=list)
    converts: list[Convert] = dataclasses.field(default_factory=list)
    callbacks: list[str] = dataclasses.field(default_factory=list)

    def _absorb(self, child: "Counts", mult_lo: int, mult_hi: int) -> None:
        self.flops_lo += mult_lo * child.flops_lo
        self.flops_hi += mult_hi * child.flops_hi
        self.dead_flops += max(mult_lo, mult_hi) * child.dead_flops
        self.dead_eqns += child.dead_eqns
        self.unbounded_loops += child.unbounded_loops
        self.contractions.extend(child.contractions)
        self.converts.extend(child.converts)
        self.callbacks.extend(child.callbacks)


def _aval(v) -> Any:
    return getattr(v, "aval", None)


def dot_general_flops(eqn) -> int:
    """2 * |out| * contracted extent (batch dims live in |out|)."""
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = _aval(eqn.invars[0]).shape
    out_shape = _aval(eqn.outvars[0]).shape
    contracted = math.prod(lhs_shape[d] for d in lhs_contract)
    return 2 * math.prod(out_shape) * contracted


def conv_flops(eqn) -> int:
    """Dilation-aware conv MACs*2 (see module docstring)."""
    dn = eqn.params["dimension_numbers"]
    lhs_shape = _aval(eqn.invars[0]).shape
    rhs_shape = _aval(eqn.invars[1]).shape
    out_shape = _aval(eqn.outvars[0]).shape
    spatial = len(dn.lhs_spec) - 2
    lhs_dil = eqn.params.get("lhs_dilation") or (1,) * spatial
    fgc = eqn.params.get("feature_group_count", 1)
    del fgc  # rhs input-feature dim is already per-group
    pairs = 1
    for i in range(spatial):
        k_i = rhs_shape[dn.rhs_spec[2 + i]]
        if lhs_dil[i] > 1:
            o_i = lhs_shape[dn.lhs_spec[2 + i]]
        else:
            o_i = out_shape[dn.out_spec[2 + i]]
        pairs *= o_i * k_i
    out_batch = out_shape[dn.out_spec[0]]
    c_out = out_shape[dn.out_spec[1]]
    cin_per_group = rhs_shape[dn.rhs_spec[1]]
    return 2 * out_batch * c_out * cin_per_group * pairs


def _contraction_flops(eqn) -> int:
    if eqn.primitive.name == "dot_general":
        return dot_general_flops(eqn)
    return conv_flops(eqn)


def _grid_size(eqn) -> int:
    grid = eqn.params["grid_mapping"].grid
    return math.prod(int(g) for g in grid) if grid else 1


def _sub_jaxprs(params) -> list:
    """Generic sub-jaxpr discovery for call-like primitives.

    Returns at most one jaxpr: ``jaxpr`` / ``call_jaxpr`` / ``fun_jaxpr``
    on a call-like primitive name the *same* program, so recursing into
    more than one would double-count.
    """
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        j = params.get(key)
        if isinstance(j, jcore.Jaxpr | jcore.ClosedJaxpr):
            return [j]
    return []


def _open(j) -> jcore.Jaxpr:
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _walk(
    jaxpr: jcore.Jaxpr,
    live_outs: list[bool] | None,
    *,
    in_cond: bool,
    path: str,
) -> Counts:
    """Count one (open) jaxpr. ``live_outs[i]`` says whether outvar i is
    demanded by the caller; ``None`` means all-live (pallas kernels,
    loop bodies — where per-output liveness can't be propagated safely).
    """
    counts = Counts()

    live: set = set()
    outvars = jaxpr.outvars
    if live_outs is None:
        live_outs = [True] * len(outvars)
    for v, is_live in zip(outvars, live_outs, strict=True):
        if is_live and isinstance(v, jcore.Var):
            live.add(v)

    for eqn in reversed(jaxpr.eqns):
        prim = eqn.primitive.name
        eqn_live = (
            live_outs is None
            or bool(eqn.effects)
            or any(isinstance(v, jcore.Var) and v in live for v in eqn.outvars)
        )
        here = f"{path}/{prim}" if path else prim

        if not eqn_live:
            counts.dead_eqns += 1
            if prim in _CONTRACTIONS:
                counts.dead_flops += _contraction_flops(eqn)
            # dead sub-programs contribute nothing; don't recurse
            continue

        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                live.add(v)

        if prim in _CONTRACTIONS:
            flops = _contraction_flops(eqn)
            counts.flops_lo += flops
            counts.flops_hi += flops
            counts.contractions.append(
                Contraction(
                    prim=prim,
                    operand_dtypes=tuple(
                        str(_aval(v).dtype) for v in eqn.invars[:2]
                    ),
                    out_dtype=str(_aval(eqn.outvars[0]).dtype),
                    flops=flops,
                    mult=1,
                    in_cond=in_cond,
                    path=here,
                )
            )
        elif prim == "convert_element_type":
            counts.converts.append(
                Convert(
                    src=str(_aval(eqn.invars[0]).dtype),
                    dst=str(eqn.params["new_dtype"]),
                    path=here,
                )
            )
        elif "callback" in prim or prim in _CALLBACK_PRIMS:
            counts.callbacks.append(here)
        elif prim == "cond":
            branches = eqn.params["branches"]
            kids = [
                _walk(_open(b), [True] * len(eqn.outvars),
                      in_cond=True, path=f"{here}[{i}]")
                for i, b in enumerate(branches)
            ]
            lo = min(k.flops_lo for k in kids)
            hi = max(k.flops_hi for k in kids)
            counts.flops_lo += lo
            counts.flops_hi += hi
            for k in kids:
                counts.dead_flops += k.dead_flops
                counts.dead_eqns += k.dead_eqns
                counts.unbounded_loops += k.unbounded_loops
                counts.contractions.extend(k.contractions)
                counts.converts.extend(k.converts)
                counts.callbacks.extend(k.callbacks)
        elif prim == "scan":
            length = int(eqn.params["length"])
            kid = _walk(_open(eqn.params["jaxpr"]), None,
                        in_cond=in_cond, path=f"{here}x{length}")
            kid.contractions = [
                dataclasses.replace(c, mult=c.mult * length)
                for c in kid.contractions
            ]
            counts._absorb(kid, length, length)
        elif prim == "while":
            counts.unbounded_loops += 1
            for j, tag in ((eqn.params["cond_jaxpr"], "cond"),
                           (eqn.params["body_jaxpr"], "body")):
                kid = _walk(_open(j), None, in_cond=in_cond,
                            path=f"{here}.{tag}")
                counts._absorb(kid, 1, 1)
        elif prim == "pallas_call":
            gsize = _grid_size(eqn)
            kid = _walk(_open(eqn.params["jaxpr"]), None,
                        in_cond=in_cond, path=f"{here}x{gsize}")
            kid.contractions = [
                dataclasses.replace(c, mult=c.mult * gsize)
                for c in kid.contractions
            ]
            counts._absorb(kid, gsize, gsize)
        else:
            subs = _sub_jaxprs(eqn.params)
            for j in subs:
                opened = _open(j)
                if len(opened.outvars) == len(eqn.outvars):
                    sub_live = [
                        isinstance(v, jcore.Var) and v in live
                        or not isinstance(v, jcore.Var)
                        for v in eqn.outvars
                    ]
                else:
                    sub_live = None
                kid = _walk(opened, sub_live, in_cond=in_cond, path=here)
                counts._absorb(kid, 1, 1)

    return counts


def count(closed: jcore.ClosedJaxpr, *, name: str = "") -> Counts:
    """Measure a ClosedJaxpr (all outputs live)."""
    return _walk(closed.jaxpr, [True] * len(closed.jaxpr.outvars),
                 in_cond=False, path=name)
