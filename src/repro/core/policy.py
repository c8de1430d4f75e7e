"""ssProp policy configuration: per-site rules + scheduled programs.

Three layers, smallest first:

* :class:`SsPropPolicy` — the static (hashable) config for ONE call
  site: *how* that site's backward gradients are sparsified.
* :class:`PolicyRules` — a name-keyed rule table (glob patterns over
  site names, the same pattern ``repro/dist/sharding.py`` uses for
  partition specs) mapping sites to per-site policies. Resolved once
  per model against the model's enumerated site names into a
  :class:`SitePolicies` table.
* :class:`PolicyProgram` — rules + a first-class
  :class:`~repro.core.schedulers.Schedule`: the one control surface the
  train loop consumes. ``program.resolve(sites).policies_for_step(step)``
  replaces the old manual ``bucketed(drop_rate_for_step(...))`` dance.

Shape-static requirement
------------------------
XLA requires static shapes, so the *keep count* K must be a Python int
at trace time. The drop-rate schedule therefore lives outside jit: the
train loop asks the resolved program for the current step's policies,
which are quantized through the schedule's ``rate_buckets`` and retrace
(cached per bucket). For the paper's 2-epoch bar scheduler this means
exactly two compiled executables: dense (scale 0) and sparse (scale 1).

Site names
----------
Each model assigns a stable name to every sparsifiable call site
(``models/model.py::site_names``, ``models/resnet.py::site_names``,
``models/ddpm.py::site_names``): transformer stacks use
``layer_{i}/{attn|self|cross}/{q,k,v,o}``, ``layer_{i}/mlp/{up,gate,
down}``, ``layer_{i}/moe/...``, ``layer_{i}/ssm/{in_proj,out_proj}``;
CNNs use ``stem``, ``block_{i}/conv1`` etc. Rule patterns are
fnmatch-style globs over those names, plus brace sets with negative
indices and ranges resolved against the model depth:
``layer_{0,-1}/*`` (first and last layer), ``layer_{2..5}/mlp/*``,
``block_*/conv{1,2}``. First matching rule wins; unmatched sites get
the table's ``default``.
"""
from __future__ import annotations

from collections.abc import Iterable, Sequence
import dataclasses
import fnmatch
import re

from repro.core.schedulers import Constant, Schedule, SCHEDULE_NAMES


@dataclasses.dataclass(frozen=True)
class SsPropPolicy:
    """Static configuration for scheduled sparse back-propagation.

    Attributes:
      drop_rate: fraction of output channels whose gradients are dropped
        in the *current* compiled step. 0.0 disables sparsification.
      granularity: ``"channel"`` = per-channel top-k (paper-faithful);
        ``"block"`` = top-k over contiguous channel blocks of
        ``block_size`` (TPU/MXU-native adaptation, see DESIGN.md §3).
      block_size: channel-block width for ``granularity="block"``.
        128 matches the TPU lane width / MXU tile.
      selection: ``"topk"`` (paper) or ``"random"`` (Fig. 2(b) ablation).
      scheduler: legacy string name of the schedule that produced this
        rate — carried for logging and FLOPs accounting only; programs
        carry a first-class :class:`~repro.core.schedulers.Schedule`
        instead. Validated against the schedule registry at
        construction so a typo fails here, not deep in the train loop.
      target_rate: the schedule's target drop rate for this site (e.g.
        0.8 for the paper's bar schedule; 0.0 pins the site dense).
      rate_buckets: allowed compiled drop rates. Scheduled rates are
        rounded to the nearest bucket so the jit cache stays small.
      mask_mode: if True, dropped channels are zeroed but matmuls stay
        full-size (reference semantics; no FLOPs saved — used by tests
        and as the XLA-autodiff-visible fallback). If False, matmuls
        shrink to the kept channels (gather mode, FLOPs actually drop).
      sparsify_dx / sparsify_dw: apply sparsity to the input-gradient /
        weight-gradient matmul. Paper uses both.
      use_pallas: route a dense site's shrunk backward matmuls through
        the Pallas gathered-matmul kernels (TPU target; interpret-mode
        on CPU) rather than plain jnp gather+dot. Conv sites ignore it:
        they take XLA's gathered conv, which beat the Pallas conv
        kernels on a TPU v5e at every ResNet-18 site that drops a block.
      seed: RNG seed for ``selection="random"``.
    """

    drop_rate: float = 0.0
    granularity: str = "channel"  # "channel" | "block"
    block_size: int = 128
    selection: str = "topk"  # "topk" | "random"
    scheduler: str = "epoch_bar"  # see schedulers.SCHEDULES
    target_rate: float = 0.8
    rate_buckets: tuple[float, ...] = (0.0, 0.25, 0.5, 0.8, 0.95)
    mask_mode: bool = False
    sparsify_dx: bool = True
    sparsify_dw: bool = True
    use_pallas: bool = False
    tp_shards: int = 0  # >0: TP-local per-shard top-k (comm-free gather;
    #   equal k per shard -> load-balanced shrunk matmuls). §Perf iter 1.
    bwd_dtype: str = ""  # "bfloat16": backward matmuls/psums in bf16
    #   (halves the fp32 cotangent all-reduce volume). §Perf iter 5.
    seed: int = 0

    def __post_init__(self):
        if not 0.0 <= self.drop_rate < 1.0:
            raise ValueError(f"drop_rate must be in [0, 1), got {self.drop_rate}")
        if self.granularity not in ("channel", "block"):
            raise ValueError(f"bad granularity {self.granularity!r}")
        if self.selection not in ("topk", "random"):
            raise ValueError(f"bad selection {self.selection!r}")
        if self.scheduler not in SCHEDULE_NAMES:
            raise ValueError(
                f"unknown scheduler {self.scheduler!r}; "
                f"known: {sorted(SCHEDULE_NAMES)}"
            )

    @property
    def active(self) -> bool:
        return self.drop_rate > 0.0

    def keep_count(self, channels: int) -> int:
        """Number of channels (or blocks) retained for ``channels`` outputs.

        Per-channel: K = max(1, round((1-D) * C)).
        Block: computed over ceil(C / block_size) blocks, at least 1 block.
        """
        if self.granularity == "channel":
            return max(1, int(round((1.0 - self.drop_rate) * channels)))
        nblocks = -(-channels // self.block_size)
        return max(1, int(round((1.0 - self.drop_rate) * nblocks)))

    def with_rate(self, rate: float) -> "SsPropPolicy":
        return dataclasses.replace(self, drop_rate=float(rate))

    def with_target(self, rate: float) -> "SsPropPolicy":
        """Same knobs, retargeted to ``rate`` (and currently at it)."""
        return dataclasses.replace(
            self, drop_rate=float(rate), target_rate=float(rate)
        )

    def bucketed(self, rate: float) -> "SsPropPolicy":
        """Round ``rate`` to the nearest allowed bucket and return a policy."""
        best = min(self.rate_buckets, key=lambda b: abs(b - rate))
        return self.with_rate(best)


DENSE = SsPropPolicy(drop_rate=0.0, target_rate=0.0)
"""The canonical "never sparsify" policy — the one definition of dense.

Use this as the default everywhere a policy parameter is optional; its
``target_rate`` is pinned to 0 so a program can never schedule it
sparse.
"""


def paper_default(drop_rate: float = 0.8) -> SsPropPolicy:
    """The paper's winning configuration: channel top-k + 2-epoch bar."""
    return SsPropPolicy(
        drop_rate=drop_rate,
        granularity="channel",
        selection="topk",
        scheduler="epoch_bar",
        target_rate=drop_rate,
    )


def tpu_default(drop_rate: float = 0.8) -> SsPropPolicy:
    """TPU-native configuration: 128-channel-block top-k (DESIGN.md §3)."""
    return SsPropPolicy(
        drop_rate=drop_rate,
        granularity="block",
        block_size=128,
        selection="topk",
        scheduler="epoch_bar",
        target_rate=drop_rate,
    )


# ----------------------------------------------------------------------
# site tables
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SitePolicies:
    """A resolved site → policy table (hashable, jit-cache-key safe).

    The per-model output of :meth:`PolicyRules.resolve`: one entry per
    enumerated call site. Lookups of names outside the table fall back
    to ``default`` — model code can therefore thread a ``SitePolicies``
    anywhere a plain :class:`SsPropPolicy` is accepted and every named
    call site picks up its own policy via :func:`policy_for`.
    """

    entries: tuple[tuple[str, SsPropPolicy], ...]
    default: SsPropPolicy = DENSE

    def __post_init__(self):
        object.__setattr__(self, "_table", dict(self.entries))

    def __getitem__(self, name: str) -> SsPropPolicy:
        return self._table.get(name, self.default)

    def __contains__(self, name: str) -> bool:
        return name in self._table

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.entries)

    def scoped(self, prefix: str) -> "SitePolicies":
        """The sub-table under ``prefix + "/"``, names stripped of it.

        ``table.scoped("layer_3")["attn/q"] == table["layer_3/attn/q"]``.
        """
        cut = len(prefix) + 1
        sub = tuple(
            (n[cut:], p)
            for n, p in self.entries
            if n.startswith(prefix + "/")
        )
        return SitePolicies(sub, default=self.default)

    def uniform(self) -> SsPropPolicy | None:
        """The single policy if every entry (and the default) agrees."""
        pols = {p for _, p in self.entries} | {self.default}
        return next(iter(pols)) if len(pols) == 1 else None


PolicyLike = SsPropPolicy | SitePolicies


def policy_for(policy: PolicyLike, site: str) -> SsPropPolicy:
    """Resolve the policy for one named call site.

    A plain :class:`SsPropPolicy` applies to every site (the legacy
    global-policy path, bit-exact by construction); a
    :class:`SitePolicies` table looks the site up by name.
    """
    if isinstance(policy, SitePolicies):
        return policy[site]
    return policy


# ----------------------------------------------------------------------
# rule patterns
# ----------------------------------------------------------------------


_BRACE = re.compile(r"\{([^{}]*)\}")
_RANGE = re.compile(r"^(-?\d+)\.\.(-?\d+)$")
_INT = re.compile(r"^-?\d+$")


def _resolve_index(value: int, depth: int | None, pattern: str) -> int:
    if value < 0:
        if depth is None:
            raise ValueError(
                f"pattern {pattern!r} uses a negative index but the model "
                "has no depth to resolve it against"
            )
        value += depth
    return value


def expand_pattern(pattern: str, depth: int | None = None) -> tuple[str, ...]:
    """Expand brace sets into plain glob patterns.

    Items in ``{...}`` may be literals (``{conv1,conv2}``), integers —
    negative ones resolve against ``depth``, Python-style
    (``layer_{0,-1}``) — or inclusive ranges (``layer_{2..5}``,
    ``layer_{0..-2}``). Multiple groups expand as a cartesian product.
    """
    m = _BRACE.search(pattern)
    if not m:
        return (pattern,)
    head, tail = pattern[: m.start()], pattern[m.end():]
    items = []
    for part in m.group(1).split(","):
        part = part.strip()
        rm = _RANGE.match(part)
        if rm:
            lo = _resolve_index(int(rm.group(1)), depth, pattern)
            hi = _resolve_index(int(rm.group(2)), depth, pattern)
            items.extend(str(v) for v in range(lo, hi + 1))
        elif _INT.match(part):
            items.append(str(_resolve_index(int(part), depth, pattern)))
        else:
            items.append(part)
    out = []
    for it in items:
        out.extend(expand_pattern(head + it + tail, depth))
    return tuple(out)


def pattern_matches(pattern: str, site: str, depth: int | None = None) -> bool:
    """fnmatch-style match of one rule pattern against a site name."""
    return any(
        fnmatch.fnmatchcase(site, glob) for glob in expand_pattern(pattern, depth)
    )


# ----------------------------------------------------------------------
# rule table
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PolicyRules:
    """Ordered (pattern, policy) rules over site names — first match wins.

    The sparsity analogue of the ``dist/sharding.py`` partition-spec
    rule table: mesh-independent rules, resolved once per model against
    its enumerated sites. A rule's policy carries the site's *target*
    rate (``target_rate``); the schedule scales every site between 0
    and its own target in lock-step.
    """

    rules: tuple[tuple[str, SsPropPolicy], ...]
    default: SsPropPolicy = DENSE

    @classmethod
    def single(cls, policy: SsPropPolicy) -> "PolicyRules":
        """The trivial one-rule program: ``policy`` at every site."""
        return cls(rules=(("*", policy),), default=policy)

    @classmethod
    def of(cls, *rules, base: SsPropPolicy, default: SsPropPolicy | None = None):
        """Build rules from (pattern, rate-or-policy) pairs.

        A float rate becomes ``base.with_target(rate)`` — so every site
        shares ``base``'s granularity/selection knobs and differs only
        in its target rate. ``default`` falls back to dense.
        """
        rows = []
        for pattern, rule in rules:
            if not isinstance(rule, SsPropPolicy):
                rule = base.with_target(float(rule))
            rows.append((pattern, rule))
        return cls(
            rules=tuple(rows),
            default=base.with_target(0.0) if default is None else default,
        )

    @classmethod
    def parse(cls, text: str, base: SsPropPolicy) -> "PolicyRules":
        """Parse the CLI mini-grammar: ``"pattern=rate;pattern=rate"``.

        ``rate`` is a float target drop rate or the word ``dense``
        (= 0.0). Example::

            layer_{0,-1}/*=dense;*/attn/*=0.5;*=0.8
        """
        rows = []
        for clause in text.split(";"):
            clause = clause.strip()
            if not clause:
                continue
            pattern, _, rate = clause.rpartition("=")
            if not pattern:
                raise ValueError(f"bad rule clause {clause!r} (want pattern=rate)")
            rows.append(
                (pattern, 0.0 if rate.strip() == "dense" else float(rate))
            )
        return cls.of(*rows, base=base)

    def resolve(
        self, sites: Sequence[str], *, depth: int | None = None
    ) -> SitePolicies:
        """Assign every enumerated site its policy (first match wins)."""
        entries = []
        for site in sites:
            for pattern, pol in self.rules:
                if pattern_matches(pattern, site, depth):
                    entries.append((site, pol))
                    break
            else:
                entries.append((site, self.default))
        return SitePolicies(tuple(entries), default=self.default)


# ----------------------------------------------------------------------
# programs
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PolicyProgram:
    """Rules + schedule: the one ssProp control surface.

    ``program.resolve(sites, depth=...)`` binds the rules to a concrete
    model; the :class:`ResolvedProgram` then answers
    ``policies_for_step(step)`` for the train loop and per-site FLOPs
    questions for the benchmarks.
    """

    rules: PolicyRules
    schedule: Schedule

    @classmethod
    def single(
        cls, policy: SsPropPolicy, schedule: Schedule | None = None
    ) -> "PolicyProgram":
        """The trivial program: one global policy, optionally scheduled.

        Without a schedule the program runs *exactly this policy* every
        step (a :class:`~repro.core.schedulers.Constant` at its
        ``drop_rate`` — so a dense policy stays dense regardless of its
        legacy ``target_rate`` field), which is bit-exact with threading
        the bare policy. With a schedule the policy's ``target_rate``
        is the peak the schedule modulates toward.
        """
        if schedule is None:
            policy = policy.with_target(policy.drop_rate)
            if policy.drop_rate not in policy.rate_buckets:
                # keep the bit-exactness promise for off-bucket rates:
                # the policy's own rate is always a legal bucket
                policy = dataclasses.replace(
                    policy,
                    rate_buckets=tuple(
                        sorted((*policy.rate_buckets, policy.drop_rate))
                    ),
                )
            schedule = Constant(
                target=policy.target_rate, rate_buckets=policy.rate_buckets
            )
        return cls(rules=PolicyRules.single(policy), schedule=schedule)

    def resolve(
        self, sites: Sequence[str], *, depth: int | None = None
    ) -> "ResolvedProgram":
        return ResolvedProgram(
            sites=self.rules.resolve(sites, depth=depth), schedule=self.schedule
        )


@dataclasses.dataclass(frozen=True)
class ResolvedProgram:
    """A program bound to one model's site table.

    ``sites`` holds every site at its *target* rate; per-step tables
    come from scaling each site by the schedule's (bucket-quantized)
    activation fraction. Over a whole run the number of distinct
    per-step tables — and therefore compiled executables — is bounded
    by ``len(schedule.rate_buckets)``.
    """

    sites: SitePolicies
    schedule: Schedule

    def at_scale(self, scale: float) -> SitePolicies:
        """Every site at ``site_target * scale``, bucket-quantized."""

        def mod(p: SsPropPolicy) -> SsPropPolicy:
            return p.bucketed(p.target_rate * scale)

        return SitePolicies(
            tuple((n, mod(p)) for n, p in self.sites.entries),
            default=mod(self.sites.default),
        )

    def policies_for_step(self, step: int) -> SitePolicies:
        return self.at_scale(self.schedule.scale(step))

    def with_tp_shards(self, shards) -> "ResolvedProgram":
        """Each site's policy told over how many shards its output
        channels are split: ``tp_shards = shards(site)`` where that is
        above 1 (selection then balances over the shards, and the dense
        backward takes its TP-local route), 0 where the channels are
        whole."""

        def told(site: str, p: SsPropPolicy) -> SsPropPolicy:
            n = shards(site)
            return dataclasses.replace(p, tp_shards=n if n > 1 else 0)

        return dataclasses.replace(
            self,
            sites=SitePolicies(
                tuple((n, told(n, p)) for n, p in self.sites.entries),
                default=self.sites.default,
            ),
        )

    def peak(self) -> SitePolicies:
        """The fully-on table (scale 1): what a sparse epoch runs."""
        return self.at_scale(1.0)

    def average_scale(self, total_steps: int) -> float:
        """Mean schedule activation over a run (for FLOPs accounting)."""
        if self.schedule.target <= 0.0:
            return 0.0
        return min(
            self.schedule.average_rate(total_steps) / self.schedule.target, 1.0
        )

    def average_rates(self, total_steps: int) -> dict[str, float]:
        """Per-site mean drop rate over a run — the per-site input to
        total-FLOPs accounting (each site saves at its own rate, not one
        global number)."""
        s = self.average_scale(total_steps)
        return {n: p.target_rate * s for n, p in self.sites.entries}


def site_tables_equal(tables: Iterable[SitePolicies]) -> bool:
    """True when every table in ``tables`` is identical (used by the
    scan-layers uniformity check in ``models/transformer.py``)."""
    it = iter(tables)
    first = next(it, None)
    return all(t == first for t in it)
