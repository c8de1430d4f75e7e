"""The paper's backward-FLOPs model (Eq. 6-11), plus policy-aware counts.

Counting convention (paper, "Drop Rate Lower Bound"): each Add, Sub, Mul
or Div is one FLOP; sorting is comparisons only (0 FLOPs); the importance
reduction adds ``(Bt*H_out*W_out - 1) * C_out`` FLOPs.

The ``*_ssprop`` functions take the paper's nominal drop rate; the
``*_policy`` functions take an :class:`~repro.core.policy.SsPropPolicy`
and count what the backward engine *actually* executes: block
granularity rounds the keep count to whole ``block_size`` blocks, and
the Pallas gathered kernels (dense sites only: conv sites contract
through XLA's gathered conv) pay for their 128-aligned tile padding.
The ``*_site`` functions are the per-site entry points: they accept a
resolved :class:`~repro.core.policy.SitePolicies` table plus the call
site's name, so a per-site policy program's total FLOPs are summed over
the resolved site table — each layer at its *own* keep count — rather
than one global rate.

Alongside FLOPs, :func:`conv_backward_bytes_policy` models the HBM
*traffic* of a conv backward in im2col form, the roofline bytes-moved
column; :func:`conv_backward_bytes_breakdown` also counts the fused
Pallas conv kernels' traffic, for those kernels' own checks. No engine
route runs those kernels.

These formulas drive the benchmark tables (paper Tables 4-7), the conv
roofline rows, and the property test on the drop-rate lower bound
(Eq. 10-11).
"""
from __future__ import annotations

from typing import TYPE_CHECKING

from repro.core import sparsity

if TYPE_CHECKING:
    from repro.core.policy import PolicyLike, SsPropPolicy


def _roundup(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def conv_backward_flops(
    bt: int, h_out: int, w_out: int, c_in: int, c_out: int, k: int
) -> int:
    """Eq. 6: backward FLOPs of one convolution, columnized form.

    ``(Bt*H_out*W_out) * (4*C_in*K^2 + 1) * C_out``
    """
    m = bt * h_out * w_out
    return m * (4 * c_in * k * k + 1) * c_out


def conv_backward_flops_ssprop(
    bt: int, h_out: int, w_out: int, c_in: int, c_out: int, k: int, drop_rate: float
) -> int:
    """Eq. 9 RHS: conv backward FLOPs with ssProp at ``drop_rate``.

    ``[(4MN + M)(1 - D) + M] * C_out`` with ``M = Bt*H_out*W_out`` and
    ``N = C_in*K^2``; the trailing ``M*C_out`` is the importance
    reduction overhead.
    """
    m = bt * h_out * w_out
    n = c_in * k * k
    return int(((4 * m * n + m) * (1.0 - drop_rate) + m) * c_out)


def batchnorm_backward_flops(bt: int, h: int, w: int, c: int) -> int:
    """Eq. 7: ``12*(Bt*H*W*C) + 10*C``."""
    return 12 * (bt * h * w * c) + 10 * c


def dropout_backward_flops(bt: int, h: int, w: int, c: int) -> int:
    """Eq. 8: ``2*(Bt*H*W*C)``."""
    return 2 * (bt * h * w * c)


def drop_rate_lower_bound(c_in: int, k: int) -> float:
    """Eq. 10: minimum drop rate that saves computation.

    ``D > 1 / (4*C_in*K^2 + 1)``; Eq. 11 notes this is <= ~3% for K>=3.
    """
    return 1.0 / (4 * c_in * k * k + 1)


def dense_backward_flops(m: int, d_in: int, d_out: int, bias: bool = True) -> int:
    """Backward FLOPs of ``Y[M, D_out] = X[M, D_in] @ W + b``.

    dX and dW are each a ``2*M*D_in*D_out`` FLOP matmul; the bias gradient
    is an ``M*D_out`` reduction. This is Eq. 6 with K=1 (a 1x1 conv), the
    form used for the transformer-projection extension (DESIGN.md §4).
    """
    f = 4 * m * d_in * d_out
    if bias:
        f += m * d_out
    return f


def dense_backward_flops_ssprop(
    m: int, d_in: int, d_out: int, drop_rate: float, bias: bool = True
) -> int:
    """ssProp dense backward: shrunk matmuls + importance reduction."""
    f = 4 * m * d_in * d_out * (1.0 - drop_rate)
    if bias:
        f += m * d_out * (1.0 - drop_rate)
    f += m * d_out  # importance reduction (Eq. 9's +M per channel)
    return int(f)


def _keeps_all(c_out: int, policy: "SsPropPolicy", groups: int = 1) -> bool:
    """Whether the engine contracts this site densely: its selection, over
    the shards the op balances it across, keeps every channel."""
    n_shards = sparsity.selection_shards(c_out, policy, groups)
    return sparsity.keeps_every_channel(c_out, policy, n_shards)


def kept_channels(c_out: int, policy: "SsPropPolicy") -> int:
    """Output channels whose gradients the engine actually computes.

    Channel granularity: the paper's ``max(1, round((1-D)*C))``. Block
    granularity: whole blocks, ``keep_count`` blocks × ``block_size``
    channels, capped at ``C`` — an upper bound when the ragged tail
    block is among the kept (its phantom slots are masked at runtime but
    the contraction is sized for the full block). A site that keeps every
    channel contracts densely, at ``C``.
    """
    if sparsity.keeps_every_channel(c_out, policy):
        return c_out
    if policy.granularity == "channel":
        return policy.keep_count(c_out)
    return min(c_out, policy.keep_count(c_out) * policy.block_size)


def gather_width(
    c_out: int, policy: "SsPropPolicy", n_shards: int = 1
) -> int:
    """The engine's true gathered contraction width (``Selection.k``).

    Unlike :func:`kept_channels` this is **not** capped at ``C``: with a
    ragged tail block the engine still gathers ``keep_count * block_size``
    columns (phantom slots zeroed by the ``valid`` mask), so the matmul
    is sized for whole blocks. Sharded selection (TP / grouped convs)
    keeps ``k_loc`` channels per shard with a shard-local block size —
    mirrored from :func:`repro.core.sparsity.shard_select_width` so the
    tables count exactly what the backward traces. A site that keeps
    every channel contracts densely, at ``C``.
    """
    if sparsity.keeps_every_channel(c_out, policy, n_shards):
        return c_out
    if n_shards > 1:
        k_loc, _ = sparsity.shard_select_width(c_out, policy, n_shards)
        return n_shards * k_loc
    if policy.granularity == "channel":
        return policy.keep_count(c_out)
    return policy.keep_count(c_out) * policy.block_size


def effective_drop_rate(c_out: int, policy: "SsPropPolicy") -> float:
    """The drop rate the backward actually realizes at ``c_out`` channels
    (block rounding makes this coarser than ``policy.drop_rate``)."""
    return 1.0 - kept_channels(c_out, policy) / c_out


def conv_backward_flops_policy(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "SsPropPolicy",
) -> int:
    """Eq. 9 with the engine's real keep counts instead of the nominal D.

    ``(4MN + M) * kept + M*C_out`` with ``M = Bt*H_out*W_out``,
    ``N = C_in*K^2`` and ``kept = kept_channels(C_out, policy)``. Every
    route contracts unpadded: ``use_pallas`` sends no conv to the
    kernels' 128-aligned tiles.
    """
    m = bt * h_out * w_out
    n = c_in * k * k
    sdx, sdw = policy.sparsify_dx, policy.sparsify_dw
    if not (sdx or sdw) or _keeps_all(c_out, policy):
        return conv_backward_flops(bt, h_out, w_out, c_in, c_out, k)
    kept = kept_channels(c_out, policy)
    # Eq. 6 decomposes per output element as 2N (dX) + 2N (dW) + 1 (db);
    # each side shrinks only when its sparsify_* flag is on.
    dx_term = 2 * m * n * (kept if sdx else c_out)
    dw_term = 2 * m * n * (kept if sdw else c_out)
    db_term = m * (kept if sdw else c_out)
    return int(dx_term + dw_term + db_term + m * c_out)


def dense_backward_flops_policy(
    m: int, d_in: int, d_out: int, policy: "SsPropPolicy", bias: bool = True
) -> int:
    """Dense analogue of :func:`conv_backward_flops_policy` (K=1 conv)."""
    sdx, sdw = policy.sparsify_dx, policy.sparsify_dw
    if not (sdx or sdw) or _keeps_all(d_out, policy):
        return dense_backward_flops(m, d_in, d_out, bias=bias)
    kept = kept_channels(d_out, policy)
    if policy.use_pallas and policy.granularity == "block":
        m_pad = _roundup(m, 128)
        d_pad = _roundup(d_in, 128)
        kept_pad = policy.keep_count(d_out) * policy.block_size
        gathered = 2 * m_pad * d_pad * kept_pad
        dx_term = gathered if sdx else 2 * m * d_in * d_out
        dw_term = gathered if sdw else 2 * m * d_in * d_out
    else:
        dx_term = 2 * m * d_in * (kept if sdx else d_out)
        dw_term = 2 * m * d_in * (kept if sdw else d_out)
    f = dx_term + dw_term
    if bias:
        f += m * (kept if sdw else d_out)
    return int(f + m * d_out)


def conv_backward_contraction_bounds(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "SsPropPolicy",
    *,
    groups: int = 1,
) -> tuple:
    """Exact ``(lo, hi)`` *contraction* FLOPs of one conv backward.

    The jaxpr-auditable core of :func:`conv_backward_flops_policy`: only
    ``conv_general_dilated`` / ``dot_general`` work — no bias reduction,
    no importance pass (those are elementwise and the walker doesn't
    count them). Groups-aware (``N_g = (C_in/G)*K²``), unlike the legacy
    per-site tables which predate grouped convs. Every conv route is
    unconditional, so ``lo == hi``; the interval form matches the
    walker's.

    A site whose selection keeps every channel (:func:`_keeps_all`)
    contracts densely, as at drop rate 0: ``2 * full_side``. Every other
    site takes XLA's gathered conv at the kept width, ``use_pallas`` or
    not (the conv offers the engine no Pallas lowering), and
    ``conv_backward_flops_policy == lo + db_term + M*C_out`` for
    ``groups == 1``; on the dense route the importance term ``M*C_out``
    is not paid.
    """
    m = bt * h_out * w_out
    cg = c_in // groups
    n_g = cg * k * k
    full_side = 2 * m * n_g * c_out
    sdx, sdw = policy.sparsify_dx, policy.sparsify_dw
    n_shards = sparsity.selection_shards(c_out, policy, groups)
    dense = policy.mask_mode or sparsity.keeps_every_channel(c_out, policy, n_shards)
    if dense or not (sdx or sdw):
        return (2 * full_side, 2 * full_side)

    width = gather_width(c_out, policy, n_shards)
    gathered_side = 2 * m * n_g * width

    dx_term = gathered_side if sdx else full_side
    dw_term = gathered_side if sdw else full_side
    t = int(dx_term + dw_term)
    return (t, t)


def dense_backward_contraction_bounds(
    m: int, d_in: int, d_out: int, policy: "SsPropPolicy"
) -> tuple:
    """Exact ``(lo, hi)`` contraction FLOPs of one dense backward.

    Dense analogue of :func:`conv_backward_contraction_bounds` — every
    route is unconditional, so ``lo == hi`` always; the interval form is
    kept for API symmetry. Routes mirrored from
    :func:`repro.core.backward.channel_sparse_backward` +
    :class:`repro.core.dense._DenseOp`:

    * inactive / mask_mode / a selection that keeps every channel:
      two full ``2*M*D_in*D_out`` matmuls,
    * TP fast path (``tp_shards`` divides ``D_out``, both sides
      sparsified): two unpadded gathered einsums — *before* the Pallas
      branch, so padding never applies,
    * Pallas block: gathered kernel sides at 128-padded tiles, dense
      sides unpadded,
    * Pallas channel: ``kops.matmul`` pads every operand dim to 128,
    * plain gather: unpadded matmuls at the engine's *gathered* width
      (:func:`gather_width` — whole blocks, not capped at ``D_out``).
    """
    full_side = 2 * m * d_in * d_out
    sdx, sdw = policy.sparsify_dx, policy.sparsify_dw
    n_shards = sparsity.selection_shards(d_out, policy)
    dense = policy.mask_mode or sparsity.keeps_every_channel(d_out, policy, n_shards)
    if dense or not (sdx or sdw):
        return (2 * full_side, 2 * full_side)

    width = gather_width(d_out, policy, n_shards)
    gathered_side = 2 * m * d_in * width

    if n_shards > 1 and sdx and sdw:
        # TP fast path: two unpadded shard-local einsums over the
        # (shard, kept) axes — checked before the Pallas branch.
        t = int(2 * gathered_side)
        return (t, t)
    if policy.use_pallas:
        if policy.granularity == "block":
            m_pad = _roundup(m, 128)
            d_pad = _roundup(d_in, 128)
            gathered_pad = 2 * m_pad * d_pad * width
            dx_term = gathered_pad if sdx else full_side
            dw_term = gathered_pad if sdw else full_side
        else:
            padded = (
                2 * _roundup(m, 128) * _roundup(d_in, 128) * _roundup(width, 128)
            )
            dx_term = padded if sdx else full_side
            dw_term = padded if sdw else full_side
        t = int(dx_term + dw_term)
        return (t, t)

    dx_term = gathered_side if sdx else full_side
    dw_term = gathered_side if sdw else full_side
    t = int(dx_term + dw_term)
    return (t, t)


def conv_backward_bytes_policy(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "SsPropPolicy",
    itemsize: int = 4,
    groups: int = 1,
) -> int:
    """HBM bytes one conv backward moves in im2col form.

    The FLOPs model (Eq. 6/9) says what the backward *computes*; this
    says what it *transfers* — the roofline memory term. It counts the
    im2col form's patch buffers — ``X2 [M, N]`` written then read by the
    dW product, ``dX2 [M, N]`` written by the dX product then read back
    by col2im (``M = Bt*H_out*W_out``, ``N = C_in*K²``). Those four
    ``M*N`` transfers dominate and do **not** shrink with sparsity. XLA's
    gathered conv, which the engine runs (:mod:`repro.core.conv`), builds
    no such buffer, so this bounds its traffic from above.
    Geometry is counted at stride 1 / 'SAME'-ish padding
    (``H_pad = H_out + K - 1``) — walkers don't carry strides.
    """
    parts = conv_backward_bytes_breakdown(
        bt, h_out, w_out, c_in, c_out, k, policy, fused=False, groups=groups
    )
    return sum(parts.values()) * itemsize


def conv_backward_bytes_breakdown(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "SsPropPolicy",
    *,
    fused: bool,
    groups: int = 1,
) -> dict[str, int]:
    """Per-component *element* counts behind the bytes model.

    With ``fused=False``, :func:`conv_backward_bytes_policy` is exactly
    ``sum(breakdown.values()) * itemsize``. With ``fused=True`` the
    terms mirror the fused Pallas conv kernel grids in
    :mod:`repro.kernels.gathered_matmul` — padded-image rows and
    cotangent panels re-fetched once per (tap × kept-block) grid step,
    the compact filter fetched into VMEM once, no ``[M, N]`` buffer —
    so the static checker can cross-validate them against a grid-walk
    traffic emulation of the kernel specs
    (:mod:`repro.analysis.pallas_check`). Those keys map 1:1 onto
    per-operand fetch totals of the ``conv_dw_fused`` /
    ``conv_dx_fused`` grids under sequential-grid revisit elision:

    * ``dw.xg_rows`` / ``dw.dy_panels`` / ``dw.out_flush`` — the dW
      kernel's image-row fetches, cotangent fetches, output flushes;
    * ``dx.dy_rows`` / ``dx.w2k_fetch`` / ``dx.out_writes`` — the dX
      kernel's cotangent fetches, single compact-filter fetch (its
      index map is constant), padded-image writes. ``dx.w2k_gather`` is
      the wrapper-side ``jnp.take`` that builds the compact filter —
      host of the kernel's fetch, not itself a kernel term.
    """
    m = bt * h_out * w_out
    cg = c_in // groups
    n = cg * k * k
    kept = kept_channels(c_out, policy)
    sdx = policy.active and policy.sparsify_dx
    sdw = policy.active and policy.sparsify_dw
    h_pad, w_pad = h_out + k - 1, w_out + k - 1
    x_elems = bt * c_in * h_pad * w_pad

    if not fused or k == 1:
        kept_dx = kept if sdx else c_out
        kept_dw = kept if sdw else c_out
        return {
            "mat.x_read": x_elems,               # read X to extract patches
            "mat.patch_buffers": 4 * m * n * groups,  # X2 w+r, dX2 w+r
            "mat.dy_panels": m * (kept_dx + kept_dw),  # read by each matmul
            "mat.importance": m * c_out,         # dY read for importance
            "mat.w_panels": n * kept_dx,         # W2 panels read (dX side)
            "mat.dw_write": n * c_out,           # dW written
            "mat.dx_write": x_elems,             # dX written
        }

    bs = policy.block_size
    nb = -(-c_out // bs)
    kb = policy.keep_count(c_out) if policy.active else nb
    kb_dx = kb if sdx else nb
    kb_dw = kb if sdw else nb
    m2 = bt * h_out      # dY row count (dW grid's sequential axis)
    s_ax = bt * h_pad    # padded-image row count (dX grid's outer axis)
    return {
        # dW kernel: one fetch per grid step for both streaming operands
        "dw.xg_rows": k * kb_dw * m2 * (w_pad * cg),
        "dw.dy_panels": k * kb_dw * m2 * (w_out * bs),
        "dw.out_flush": k * kb_dw * (k * cg * bs),
        # dX kernel: cotangent per (row, block, tap); filter once
        "dx.dy_rows": s_ax * kb_dx * k * (w_out * bs),
        "dx.w2k_gather": k * k * cg * kb_dx * bs,
        "dx.w2k_fetch": k * k * cg * kb_dx * bs,
        "dx.out_writes": s_ax * (w_pad * cg) * groups,
        # shared wrapper traffic
        "common.pad_image": 2 * x_elems,   # build padded row-major view
        "common.importance": m * c_out,    # dY read for importance
        "common.dw_write": n * c_out,      # dW written
        "common.dx_write": x_elems,        # dX written (border sliced off)
    }


def conv_backward_bytes_site(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "PolicyLike",
    site: str = "",
    itemsize: int = 4,
) -> int:
    """:func:`conv_backward_bytes_policy` for one named call site."""
    from repro.core.policy import policy_for

    return conv_backward_bytes_policy(
        bt, h_out, w_out, c_in, c_out, k, policy_for(policy, site),
        itemsize=itemsize,
    )


def conv_backward_flops_site(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: "PolicyLike",
    site: str = "",
) -> int:
    """:func:`conv_backward_flops_policy` for one *named* call site.

    ``policy`` may be a plain policy (the site name is ignored) or a
    resolved :class:`~repro.core.policy.SitePolicies` table — the conv
    then counts at its own site's policy. This is what makes whole-model
    FLOPs walks (``models/resnet.py::flops_per_iter``,
    ``models/ddpm.py::flops_per_iter``) per-site aware.
    """
    from repro.core.policy import policy_for

    return conv_backward_flops_policy(
        bt, h_out, w_out, c_in, c_out, k, policy_for(policy, site)
    )


def dense_backward_flops_site(
    m: int,
    d_in: int,
    d_out: int,
    policy: "PolicyLike",
    site: str = "",
    bias: bool = True,
) -> int:
    """:func:`dense_backward_flops_policy` for one named call site."""
    from repro.core.policy import policy_for

    return dense_backward_flops_policy(
        m, d_in, d_out, policy_for(policy, site), bias=bias
    )


def savings_fraction(
    dense_flops: int, ssprop_flops: int
) -> float:
    """Fraction of backward FLOPs saved by ssProp."""
    if dense_flops <= 0:
        return 0.0
    return 1.0 - ssprop_flops / dense_flops


def conv_layer_report(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    drop_rate: float,
    policy: "SsPropPolicy" = None,
) -> dict[str, float]:
    """Per-layer dict used by the benchmark tables.

    With ``policy`` the ssProp count uses the engine's real keep counts
    (:func:`conv_backward_flops_policy`); otherwise the paper's nominal
    Eq. 9 at ``drop_rate``.
    """
    dense = conv_backward_flops(bt, h_out, w_out, c_in, c_out, k)
    if policy is not None:
        sparse = conv_backward_flops_policy(bt, h_out, w_out, c_in, c_out, k, policy)
    else:
        sparse = conv_backward_flops_ssprop(bt, h_out, w_out, c_in, c_out, k, drop_rate)
    return {
        "dense_flops": dense,
        "ssprop_flops": sparse,
        "saved": savings_fraction(dense, sparse),
        "lower_bound": drop_rate_lower_bound(c_in, k),
    }
