"""Honest-savings audit: jaxpr-measured backward FLOPs vs analytic tables.

For every sparsifiable site of a model this module traces the *actual*
backward program (``jax.vjp`` of ``sparse_conv2d`` / ``sparse_dense``
under the site's resolved policy, abstract inputs only — nothing runs)
and counts its contractions with :mod:`repro.analysis.jaxpr_walk`. The
measured ``(lo, hi)`` interval must equal
:func:`repro.core.flops.conv_backward_contraction_bounds` /
``dense_backward_contraction_bounds`` **exactly** — those tables model
every route the engine takes, including the Pallas tile padding of dense
sites (conv sites take XLA's gathered conv, never a kernel), so any
daylight between the two means the paper-facing savings numbers are
dishonest and the audit errors.

The legacy Eq.-9 tables (``conv_backward_flops_policy`` et al., what
``benchmarks/roofline.py`` historically multiplied out) are compared as
a *sanity band*: they count kept channels rather than the engine's
gathered width, so the audit only warns when they drift more than 2x
from the measured interval — block-rounding and bookkeeping tolerance,
not a contract.

Probes are geometry-exact: convs are traced at stride 1 with
``(K-1)``-total padding so ``H_in == H_out``. The analytic tables
carry no stride parameter, so a strided site audits through its
stride-1 twin with the same output geometry — same M, N, routing and
padding, hence the same backward contraction cost the tables model.

Traces are cached on ``(geometry, policy)``; per-model audits over
ResNet/DDPM conv walks and the transformer dense walk therefore pay one
trace per distinct site geometry.
"""
from __future__ import annotations

import functools

import jax

from repro.analysis import jaxpr_walk
from repro.analysis.lints import lint_backward_counts
from repro.analysis.report import ERROR, INFO, Report, WARN
from repro.core import flops as ftab
from repro.core import sparse_conv2d, sparse_dense
from repro.core.policy import PolicyLike, SsPropPolicy, policy_for

#: multiplicative sanity band for the legacy Eq.-9 tables (see module
#: docstring) — measured/legacy outside [1/2, 2] is a warning.
LEGACY_BAND = 2.0


# ----------------------------------------------------------------------
# cached probe traces
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def conv_backward_counts(
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: SsPropPolicy,
    groups: int = 1,
    dtype: str = "float32",
) -> jaxpr_walk.Counts:
    """Walker census of one conv site's backward program (trace only)."""
    pl_, pr = (k - 1) // 2, (k - 1) - (k - 1) // 2
    x = jax.ShapeDtypeStruct((bt, c_in, h_out, w_out), dtype)
    w = jax.ShapeDtypeStruct((c_out, c_in // groups, k, k), dtype)
    b = jax.ShapeDtypeStruct((c_out,), dtype)
    dy = jax.ShapeDtypeStruct((bt, c_out, h_out, w_out), dtype)

    def bwd(x_, w_, b_, dy_):
        _, vjp = jax.vjp(
            lambda xa, wa, ba: sparse_conv2d(
                xa,
                wa,
                ba,
                stride=1,
                padding=((pl_, pr), (pl_, pr)),
                groups=groups,
                policy=policy,
            ),
            x_,
            w_,
            b_,
        )
        return vjp(dy_)

    closed = jax.make_jaxpr(bwd)(x, w, b, dy)
    return jaxpr_walk.count(closed, name=f"conv[{c_in}->{c_out}]k{k}")


@functools.lru_cache(maxsize=None)
def dense_backward_counts(
    m: int,
    d_in: int,
    d_out: int,
    policy: SsPropPolicy,
    dtype: str = "bfloat16",
) -> jaxpr_walk.Counts:
    """Walker census of one dense site's backward program (trace only)."""
    x = jax.ShapeDtypeStruct((m, d_in), dtype)
    w = jax.ShapeDtypeStruct((d_in, d_out), dtype)
    b = jax.ShapeDtypeStruct((d_out,), dtype)
    dy = jax.ShapeDtypeStruct((m, d_out), dtype)

    def bwd(x_, w_, b_, dy_):
        _, vjp = jax.vjp(
            lambda xa, wa, ba: sparse_dense(xa, wa, ba, policy=policy),
            x_,
            w_,
            b_,
        )
        return vjp(dy_)

    closed = jax.make_jaxpr(bwd)(x, w, b, dy)
    return jaxpr_walk.count(closed, name=f"dense[{d_in}->{d_out}]")


def clear_cache() -> None:
    """Drop cached traces (tests that monkeypatch the engine need this)."""
    conv_backward_counts.cache_clear()
    dense_backward_counts.cache_clear()


# ----------------------------------------------------------------------
# per-site audits
# ----------------------------------------------------------------------


def _legacy_band_check(
    report: Report, site: str, lo: int, hi: int, legacy: int, dense_ref: int
) -> None:
    mid = (lo + hi) / 2 or 1
    ratio = legacy / mid
    sev = INFO if 1 / LEGACY_BAND <= ratio <= LEGACY_BAND else WARN
    report.add(
        "savings",
        sev,
        site,
        f"measured backward contraction FLOPs in [{lo:,}, {hi:,}] "
        f"({mid / dense_ref:.3f}x dense); legacy table {legacy:,} "
        f"({ratio:.2f}x measured mid)",
        flops_lo=lo,
        flops_hi=hi,
        legacy=legacy,
        dense_ref=dense_ref,
        ratio_vs_dense=mid / dense_ref,
    )


def audit_conv_site(
    report: Report,
    site: str,
    bt: int,
    h_out: int,
    w_out: int,
    c_in: int,
    c_out: int,
    k: int,
    policy: SsPropPolicy,
    *,
    groups: int = 1,
    dtype: str = "float32",
) -> jaxpr_walk.Counts:
    """Audit one conv site: measured == analytic bounds, lints, band."""
    counts = conv_backward_counts(
        bt, h_out, w_out, c_in, c_out, k, policy, groups, dtype
    )
    lo, hi = ftab.conv_backward_contraction_bounds(
        bt, h_out, w_out, c_in, c_out, k, policy, groups=groups
    )
    if (counts.flops_lo, counts.flops_hi) != (lo, hi):
        report.add(
            "savings",
            ERROR,
            site,
            f"jaxpr backward FLOPs ({counts.flops_lo:,}, "
            f"{counts.flops_hi:,}) != analytic bounds ({lo:,}, {hi:,})",
            measured=(counts.flops_lo, counts.flops_hi),
            analytic=(lo, hi),
        )
    if groups == 1:
        legacy = ftab.conv_backward_flops_policy(
            bt, h_out, w_out, c_in, c_out, k, policy
        )
        dense_ref = ftab.conv_backward_flops(bt, h_out, w_out, c_in, c_out, k)
        _legacy_band_check(report, site, lo, hi, legacy, dense_ref)
    lint_backward_counts(report, site, counts, policy)
    return counts


def audit_dense_site(
    report: Report,
    site: str,
    m: int,
    d_in: int,
    d_out: int,
    policy: SsPropPolicy,
    *,
    dtype: str = "bfloat16",
) -> jaxpr_walk.Counts:
    """Audit one dense site: measured == analytic bounds, lints, band."""
    counts = dense_backward_counts(m, d_in, d_out, policy, dtype)
    lo, hi = ftab.dense_backward_contraction_bounds(m, d_in, d_out, policy)
    if (counts.flops_lo, counts.flops_hi) != (lo, hi):
        report.add(
            "savings",
            ERROR,
            site,
            f"jaxpr backward FLOPs ({counts.flops_lo:,}, "
            f"{counts.flops_hi:,}) != analytic bounds ({lo:,}, {hi:,})",
            measured=(counts.flops_lo, counts.flops_hi),
            analytic=(lo, hi),
        )
    legacy = ftab.dense_backward_flops_policy(m, d_in, d_out, policy)
    dense_ref = ftab.dense_backward_flops(m, d_in, d_out)
    _legacy_band_check(report, site, lo, hi, legacy, dense_ref)
    lint_backward_counts(report, site, counts, policy)
    return counts


# ----------------------------------------------------------------------
# per-model audits
# ----------------------------------------------------------------------


def audit_resnet(
    name: str,
    image,
    policy: PolicyLike,
    *,
    batch: int,
) -> Report:
    """Audit every conv site of a ResNet variant at one input shape."""
    from repro.models import resnet

    report = Report(f"savings:{name}")
    for site, c_in, c_out, k, h_out, w_out in resnet.iter_conv_shapes(
        name, image
    ):
        audit_conv_site(
            report, site, batch, h_out, w_out, c_in, c_out, k,
            policy_for(policy, site),
        )
    return report


def audit_ddpm(
    image,
    policy: PolicyLike,
    *,
    batch: int,
    base: int = 64,
) -> Report:
    """Audit every conv site of the DDPM UNet at one input shape."""
    from repro.models import ddpm

    report = Report("savings:ddpm")
    for site, c_in, c_out, k, h_out, w_out in ddpm.iter_conv_shapes(
        image, base
    ):
        audit_conv_site(
            report, site, batch, h_out, w_out, c_in, c_out, k,
            policy_for(policy, site),
        )
    return report


def audit_lm(
    cfg,
    policy: PolicyLike,
    *,
    batch: int,
    seq: int,
) -> Report:
    """Audit every dense projection geometry of a transformer config.

    Sites come from :func:`repro.models.transformer.iter_dense_shapes`
    (depth-aggregated, one audit per distinct geometry); the per-site
    policy is resolved against the representative ``layer_{si}/...``
    path, matching what ``stack_apply`` does at that depth.
    """
    from repro.models import transformer

    report = Report(f"savings:{cfg.name}")
    for site, m, d_in, d_out, count in transformer.iter_dense_shapes(
        cfg, batch, seq
    ):
        counts = audit_dense_site(
            report, site, m, d_in, d_out, policy_for(policy, site),
            dtype=cfg.dtype,
        )
        report.add(
            "savings",
            INFO,
            site,
            f"x{count} layers: per-layer measured "
            f"[{counts.flops_lo:,}, {counts.flops_hi:,}]",
            count=count,
            flops_lo=counts.flops_lo,
            flops_hi=counts.flops_hi,
        )
    return report


def lm_site_flops(cfg, policy: PolicyLike, *, batch: int, seq: int):
    """Jaxpr-derived per-site backward contraction FLOPs for roofline.

    Returns ``[(site, count, fwd_flops, bwd_lo, bwd_hi), ...]`` — the
    measured (not 6ND) per-site numbers ``benchmarks/roofline.py``
    consumes. ``fwd_flops`` is the plain ``2*M*D_in*D_out`` forward
    cost; the backward interval comes from the traced program.
    """
    from repro.models import transformer

    rows = []
    for site, m, d_in, d_out, count in transformer.iter_dense_shapes(
        cfg, batch, seq
    ):
        counts = dense_backward_counts(
            m, d_in, d_out, policy_for(policy, site), cfg.dtype
        )
        rows.append(
            (site, count, 2 * m * d_in * d_out, counts.flops_lo,
             counts.flops_hi)
        )
    return rows
