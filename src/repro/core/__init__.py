"""ssProp core: scheduled channel-sparse back-propagation.

The paper's contribution as a composable JAX module:

* :mod:`repro.core.backward`   — the unified channel-sparse backward
  engine: one pipeline (importance → selection → gather → shrunk
  contraction → compact-gradient scatter, plus the mask-mode oracle,
  ``bwd_dtype`` casting, TP-local selection, and Pallas routing) that
  both ops below plug into via :class:`~repro.core.backward.ChannelSparseOp`.
* :mod:`repro.core.sparsity`   — channel importance + top-k selection
  (:class:`~repro.core.sparsity.Selection` carries the ragged-tail
  validity mask and per-shard balanced form).
* :mod:`repro.core.schedulers` — first-class drop-rate schedules
  (constant, linear, cosine, bar, 2-epoch bar, periodic bar) with
  per-schedule ``rate(step)`` / ``average_rate`` / bucket quantization.
* :mod:`repro.core.dense`      — ``sparse_dense``: matmul adapter over
  the engine (custom_vjp).
* :mod:`repro.core.conv`       — ``sparse_conv2d``: convolution adapter
  over the engine; a site that drops a block contracts through XLA's
  gathered conv, with or without ``use_pallas``.
* :mod:`repro.core.flops`      — the paper's FLOPs model (Eq. 6-11) and
  the policy-aware counts (block rounding, Pallas tile padding).
* :mod:`repro.core.policy`     — the policy program surface:
  ``SsPropPolicy`` (one site's config), ``PolicyRules`` (site-name rule
  table), ``PolicyProgram`` / ``ResolvedProgram`` (rules + schedule,
  the train loop's one control object) and ``SitePolicies`` (the
  resolved site → policy table threaded through the models).
"""
from repro.core import flops
from repro.core.backward import ChannelSparseOp, channel_sparse_backward
from repro.core.conv import sparse_conv2d
from repro.core.dense import sparse_dense
from repro.core.policy import (
    DENSE,
    PolicyProgram,
    PolicyRules,
    ResolvedProgram,
    SitePolicies,
    SsPropPolicy,
    policy_for,
)
from repro.core.schedulers import (
    SCHEDULES,
    Bar,
    Constant,
    Cosine,
    EpochBar,
    Linear,
    PeriodicBar,
    Schedule,
    bar_schedule,
    constant_schedule,
    cosine_schedule,
    drop_rate_for_step,
    epoch_bar_schedule,
    linear_schedule,
    make_schedule,
)
from repro.core.sparsity import (
    Selection,
    channel_importance,
    select_topk_blocks,
    select_topk_channels,
)

__all__ = [
    "SsPropPolicy",
    "DENSE",
    "PolicyRules",
    "PolicyProgram",
    "ResolvedProgram",
    "SitePolicies",
    "policy_for",
    "Schedule",
    "Constant",
    "Linear",
    "Cosine",
    "Bar",
    "EpochBar",
    "PeriodicBar",
    "SCHEDULES",
    "make_schedule",
    "Selection",
    "ChannelSparseOp",
    "channel_sparse_backward",
    "sparse_dense",
    "sparse_conv2d",
    "channel_importance",
    "select_topk_channels",
    "select_topk_blocks",
    "constant_schedule",
    "linear_schedule",
    "cosine_schedule",
    "bar_schedule",
    "epoch_bar_schedule",
    "drop_rate_for_step",
    "flops",
]
