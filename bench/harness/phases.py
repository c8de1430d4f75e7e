"""Attribute a traced window's device time to the training step's phases,
conv sites and ssProp stages.

The program names its layers with ``jax.named_scope``: every conv or
dense call under its site name (``block_3/conv1``), the sparse backward's
two stages under ``ssprop_select`` (importance and top-k) and
``ssprop_contract`` (the kept-channel products and their scatter), and
the optimizer's update under ``adam``. JAX writes ``transpose(...)`` into
the name of every backward operation. XLA keeps the scope path in the
``op_name`` of each instruction's metadata, so the compiled step's HLO
text names the work of every device operation of a trace (an ``XLA Ops``
event is named after its instruction):

    jit(classifier_step)/jvp(block_0/conv1)/conv_general_dilated
    jit(classifier_step)/transpose(jvp(block_0/conv1))/ssprop_select/reduce_sum
    jit(classifier_step)/adam/sub

A fusion runs the instructions of its fused computation, each with its
own ``op_name``, and XLA fuses across scopes: a sparse site's importance
(``|dY|`` summed per channel) lands in the fusion that makes ``dY`` from
the batch norm's backward, and Adam's update in the fusion that makes the
weight gradient. The fusion's own ``op_name`` names one of them. So each
operation's time is split over the scopes of the instructions it runs,
weighed by their work (:func:`work`): an elementwise or reducing
instruction the elements of the largest array it reads or writes, a
convolution or dot its multiply-adds over 64 (the MXU's multiply-adds per
cycle over the vector unit's lanes, 4 x 128 x 128 against 8 x 128). The split is an estimate; the trace measures only
the bounds that :class:`Phases` keeps beside it: the time of operations
that run a phase (or the selection) alone, and of those that run any of
it. An operation whose instructions carry no ``op_name`` (a copy that XLA
inserted, say) is ``unattributed``.

Everything works on the text and on plain tuples (``trace.Trace``), so it
can be checked on the CPU.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import re

from bench.harness import trace

PHASES = ("forward", "backward", "optimizer", "unattributed")
STAGES = {"ssprop_select": "select", "ssprop_contract": "contract"}
OPTIMIZER_SCOPE = "adam"
SITE_KEYS = ("forward", "select", "contract", "backward_other")
# A multiply-add on the MXU weighs this much of one vector-unit element.
MAC_WEIGHT = 1 / 64
# Instructions that do no work of their own inside a fusion.
FREE = frozenset({"parameter", "constant", "get-tuple-element", "tuple", "bitcast", "broadcast"})

# "%name = <shape> opcode(operands), attributes"; the name may follow ROOT.
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*?) ([a-z][a-z0-9_-]*)\((.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(\S+) ")
_ARRAY = re.compile(r"\b[a-z][a-z0-9]*\[([\d,]*)\]")
_KERNEL_LABELS = re.compile(r"dim_labels=[^_\s]+_([^-\s]+)->")
_LHS_CONTRACTING = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_OPERAND = re.compile(r"%([^\s,()]+)")
_ESCAPE = re.compile(r"\\(.)")
_PATH = re.compile(r"[/()]")


@dataclasses.dataclass(frozen=True)
class _Instruction:
    name: str
    opcode: str
    op_name: str
    dims: tuple  # of the largest array it writes
    operands: tuple
    calls: str  # the fused computation of a fusion, else ""
    contracts: str  # a convolution's kernel labels ("01io"), a dot's lhs contracting dims

    @property
    def elements(self) -> int:
        return math.prod(self.dims)


def _operand_text(rest: str) -> str:
    """The text up to the parenthesis that closes the operand list."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i]
    return rest


def _dims(shape: str) -> tuple:
    arrays = [tuple(int(d) for d in dims.split(",") if d) for dims in _ARRAY.findall(shape)]
    return max(arrays, key=math.prod, default=())


def _contracts(opcode: str, rest: str) -> str:
    m = None
    if opcode == "convolution":
        m = _KERNEL_LABELS.search(rest)
    elif opcode == "dot":
        m = _LHS_CONTRACTING.search(rest)
    return m[1] if m else ""


def _weight(i: _Instruction, dims: dict) -> float:
    """An instruction's work (see the module's docstring)."""
    operands = [dims.get(o, ()) for o in i.operands]
    if i.contracts and i.opcode == "convolution" and len(operands) == 2:
        kernel, labels = operands[1], i.contracts
        if len(kernel) == len(labels):
            return i.elements * math.prod(kernel) / kernel[labels.index("o")] * MAC_WEIGHT
    if i.contracts and i.opcode == "dot" and operands:
        lhs = operands[0]
        k = math.prod(lhs[int(d)] for d in i.contracts.split(",") if d and int(d) < len(lhs))
        return i.elements * k * MAC_WEIGHT
    return max([i.elements, *(math.prod(d) for d in operands)])


def _parse(hlo_text: str) -> dict[str, list[_Instruction]]:
    """Each computation's instructions, in order."""
    comps: dict[str, list[_Instruction]] = {}
    body = comps.setdefault("", [])  # instructions before any header
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and line.rstrip().endswith("{"):
                body = comps.setdefault(c[1], [])
            continue
        name, shape, opcode, rest = m.groups()
        meta = _OP_NAME.search(rest)
        calls = _CALLS.search(rest) if opcode == "fusion" else None
        body.append(_Instruction(
            name, opcode, _ESCAPE.sub(r"\1", meta[1]) if meta else "", _dims(shape),
            tuple(_OPERAND.findall(_operand_text(rest))), calls[1] if calls else "",
            _contracts(opcode, rest),
        ))
    return comps


def op_names(hlo_text: str) -> dict[str, str]:
    """Each instruction's name to the ``op_name`` of its metadata, for the
    instructions that have one (fused ones included)."""
    return {
        i.name: i.op_name for body in _parse(hlo_text).values() for i in body if i.op_name
    }


def work(hlo_text: str) -> dict[str, tuple[tuple[str, float], ...]]:
    """Each instruction's name to the ``(op_name, weight)`` of the work it
    runs: a fusion's are those of the instructions of its fused
    computation (nested fusions opened too), each weighing its work, with
    an ``op_name`` of several names (``a;b``) sharing it; any other instruction's is
    its own ``op_name``. Instructions of ``FREE`` opcodes and without an
    ``op_name`` weigh nothing; a fusion of nothing else falls back to its
    own ``op_name``. An instruction with no ``op_name`` runs nothing
    named."""
    comps = _parse(hlo_text)
    dims = {i.name: i.dims for body in comps.values() for i in body}
    memo: dict[str, list[tuple[str, float]]] = {}

    def fused(comp: str) -> list[tuple[str, float]]:
        if comp not in memo:
            out = []
            for i in comps.get(comp, ()):
                if i.calls:
                    out += fused(i.calls)
                elif i.op_name and i.opcode not in FREE:
                    w = _weight(i, dims)
                    names = i.op_name.split(";")
                    out += [(n, w / len(names)) for n in names]
            memo[comp] = out
        return memo[comp]

    out = {}
    for body in comps.values():
        for i in body:
            runs = fused(i.calls) if i.calls else []
            if not runs and i.op_name:
                names = i.op_name.split(";")
                runs = [(n, 1.0 / len(names)) for n in names]
            out[i.name] = tuple(runs)
    return out


def _components(op_name: str) -> list[str]:
    return [c for c in _PATH.split(op_name) if c]


def _site_in(parts: list[str], sites) -> str:
    """The first site whose components follow each other in ``parts``."""
    for i in range(len(parts)):
        for site in sites:
            sp = site.split("/")
            if parts[i : i + len(sp)] == sp:
                return site
    return ""


def phase_of(op_name: str, sites) -> tuple[str, str, str]:
    """``(phase, site, stage)`` of an operation, from its ``op_name``.

    ``phase`` is ``optimizer`` where a path component is ``adam``,
    ``backward`` where the name holds ``transpose(``, ``forward`` for any
    other name and ``unattributed`` for none; ``site`` is the conv site of
    ``sites`` that the path names, else ""; ``stage`` is ``select`` or
    ``contract`` under the sparse backward's scopes, else "".
    """
    if not op_name:
        return "unattributed", "", ""
    parts = _components(op_name)
    if OPTIMIZER_SCOPE in parts:
        return "optimizer", "", ""
    phase = "backward" if "transpose(" in op_name else "forward"
    stage = next((STAGES[p] for p in parts if p in STAGES), "")
    return phase, _site_in(parts, sites), stage


def split(runs, sites) -> dict[tuple[str, str, str], float]:
    """The shares of an operation's time by ``(phase, site, stage)``, from
    the ``(op_name, weight)`` pairs of the work it runs."""
    total = sum(w for _, w in runs)
    if total <= 0:
        return {("unattributed", "", ""): 1.0}
    shares: dict[tuple[str, str, str], float] = collections.defaultdict(float)
    for name, w in runs:
        shares[phase_of(name, sites)] += w / total
    return dict(shares)


@dataclasses.dataclass(frozen=True)
class Phases:
    """Device seconds of a traced window, summed over its operations
    clipped to the window (and over the chips), like ``kernel_s``. An
    operation's seconds are split over what it runs (:func:`split`)."""

    steps: int
    op_s: float  # every operation
    phase_s: dict  # phase -> seconds; the phases sum to op_s
    select_s: float  # backward work under ``ssprop_select``
    site_s: dict  # site -> {forward, select, contract, backward_other}
    bounds_s: dict  # phase or "select" -> [run alone, run at all]
    mixed_s: float  # operations that run more than one (phase, site, stage)
    unattributed: list  # [[instruction, seconds]] of the longest, most first

    @property
    def attributed_share(self) -> float:
        """Phase-attributed seconds over summed operation seconds."""
        return 1.0 - self.phase_s["unattributed"] / self.op_s if self.op_s > 0 else 0.0

    def per_step_ms(self) -> dict:
        """What ``phases.json`` holds: milliseconds per traced step."""
        ms = 1e3 / self.steps
        return {
            "steps": self.steps,
            "op_ms": self.op_s * ms,
            "phase_ms": {k: v * ms for k, v in self.phase_s.items()},
            "select_ms": self.select_s * ms,
            "bounds_ms": {k: [v * ms for v in b] for k, b in self.bounds_s.items()},
            "mixed_ms": self.mixed_s * ms,
            "site_ms": {s: {k: v * ms for k, v in t.items()} for s, t in self.site_s.items()},
            "attributed_share": self.attributed_share,
            "unattributed_ms": [[n, v * ms] for n, v in self.unattributed],
        }


def _select_ran(shares) -> dict:
    """``{"select": alone}`` where an operation runs selection work."""
    sel = [k for k in shares if k[0] == "backward" and k[2] == "select"]
    return {"select": len(sel) == len(shares)} if sel else {}


def reduce(tr: trace.Trace, steps: int, runs: dict, sites, top: int = 10) -> Phases:
    """Sum the window's device time by phase, site and stage.

    ``runs`` maps instruction names to the work they run (:func:`work`);
    ``sites`` are the model's conv sites, each of which gets a row.
    """
    lo, hi = trace.window(tr.spans, steps)
    phase_s = dict.fromkeys(PHASES, 0.0)
    site_s = {s: dict.fromkeys(SITE_KEYS, 0.0) for s in sites}
    bounds_s = {k: [0.0, 0.0] for k in (*PHASES[:3], "select")}
    select_s = op_s = mixed_s = 0.0
    unnamed: collections.Counter = collections.Counter()
    seen: dict[str, dict] = {}
    for ops in tr.devices.values():
        for e in ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d <= 0:
                continue
            if e.name not in seen:
                seen[e.name] = split(runs.get(e.name, ()), sites)
            shares = seen[e.name]
            op_s += d
            mixed_s += d * (len(shares) > 1)
            for (phase, site, stage), f in shares.items():
                phase_s[phase] += d * f
                if phase == "unattributed":
                    unnamed[e.name] += d * f
                if phase == "backward" and stage == "select":
                    select_s += d * f
                if site and phase in ("forward", "backward"):
                    key = "forward" if phase == "forward" else stage or "backward_other"
                    site_s[site][key] += d * f
            ran = {k[0] for k in shares}
            ran = {p: ran == {p} for p in ran} | _select_ran(shares)
            for kind, alone in ran.items():
                if kind in bounds_s:
                    bounds_s[kind][0] += d * alone
                    bounds_s[kind][1] += d
    return Phases(
        steps, op_s, phase_s, select_s, site_s, bounds_s, mixed_s,
        [[n, v] for n, v in unnamed.most_common(top)],
    )


def phase_ms(ph: Phases | None, phase: str) -> float | None:
    """Device milliseconds per traced step in ``phase``, or None where
    there is no attribution or no operation of the phase ran."""
    if ph is None or ph.steps < 1 or ph.phase_s[phase] <= 0:
        return None
    return 1e3 * ph.phase_s[phase] / ph.steps
