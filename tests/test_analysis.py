"""Tests for repro.analysis: the jaxpr walker vs the analytic FLOPs
tables (exact, over a policy × groups × dtype grid), the seeded
regressions each lint must catch (planted f32 upcast, planted host
callback, out-of-bounds index map), retrace budgets, the Pallas traffic
cross-check of the conv kernels (which no engine route runs), and the
docs/CLI static checker."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jaxpr_walk, pallas_check, retrace, savings
from repro.analysis.lints import lint_step_counts
from repro.analysis.report import ERROR, INFO, Report
from repro.core import backward
from repro.core import flops as ftab
from repro.core.policy import (
    DENSE,
    PolicyProgram,
    PolicyRules,
    paper_default,
    tpu_default,
)
from repro.core.schedulers import make_schedule
from repro.configs.registry import get_config

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(_ROOT))


def _policies():
    block = tpu_default(0.8)
    return [
        ("dense", DENSE),
        ("channel", paper_default(0.8)),
        ("block", block),
        ("block_pallas", dataclasses.replace(block, use_pallas=True)),
        (
            "block_pallas_32",
            dataclasses.replace(block, use_pallas=True, block_size=32),
        ),
    ]


@pytest.fixture(autouse=True)
def _fresh_trace_cache():
    savings.clear_cache()
    yield
    savings.clear_cache()


# ----------------------------------------------------------------------
# walker == analytic tables, exactly
# ----------------------------------------------------------------------


class TestConvAuditGrid:
    # 32 output channels keep every block of 128 or 32 channels, so the
    # block policies contract densely there; 256 drop a block on every
    # gathered and Pallas route (but two groups of one 128-channel block
    # keep both)
    @pytest.mark.parametrize("c_out", [32, 256])
    @pytest.mark.parametrize("pname,policy", _policies())
    @pytest.mark.parametrize("groups", [1, 2])
    @pytest.mark.parametrize("bwd_dtype", ["", "bfloat16"])
    def test_measured_equals_bounds(self, pname, policy, groups, bwd_dtype, c_out):
        policy = dataclasses.replace(policy, bwd_dtype=bwd_dtype)
        rep = Report("t")
        savings.audit_conv_site(
            rep, "site", 2, 8, 8, 16, c_out, 3, policy, groups=groups
        )
        assert not rep.errors(), [f.message for f in rep.errors()]

    @pytest.mark.parametrize("c_out,k", [(64, 3), (128, 3), (128, 1)])
    def test_site_that_keeps_every_channel_audits_dense(self, c_out, k):
        # ResNet-18's 64- and 128-channel sites at 0.8 (one block kept
        # of one): no Pallas kernel, no gathered product, the dense count
        pol = dataclasses.replace(tpu_default(0.8), use_pallas=True)
        rep = Report("t")
        counts = savings.audit_conv_site(rep, "site", 2, 8, 8, 64, c_out, k, pol)
        assert not rep.errors(), [f.message for f in rep.errors()]
        dense = ftab.conv_backward_contraction_bounds(2, 8, 8, 64, c_out, k, DENSE)
        assert (counts.flops_lo, counts.flops_hi) == dense
        assert ftab.gather_width(c_out, pol) == ftab.kept_channels(c_out, pol) == c_out
        assert {c.prim for c in counts.contractions} == {"conv_general_dilated"}

    def test_strided_site_audits_via_stride1_twin(self):
        # the probe is stride-1 by construction; the tables carry no
        # stride, so the same (h_out, w_out) geometry must stay exact
        rep = Report("t")
        counts = savings.audit_conv_site(
            rep, "site", 2, 4, 4, 16, 32, 3, tpu_default(0.8)
        )
        lo, hi = ftab.conv_backward_contraction_bounds(
            2, 4, 4, 16, 32, 3, tpu_default(0.8)
        )
        assert (counts.flops_lo, counts.flops_hi) == (lo, hi)
        assert not rep.errors()


class TestDenseAuditGrid:
    # 128 outputs are one 128-channel block, kept whole at 0.8
    @pytest.mark.parametrize("d_out", [128, 256])
    @pytest.mark.parametrize("pname,policy", _policies())
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_measured_equals_bounds(self, pname, policy, dtype, d_out):
        rep = Report("t")
        counts = savings.audit_dense_site(
            rep, "site", 64, 128, d_out, policy, dtype=dtype
        )
        assert not rep.errors(), [f.message for f in rep.errors()]
        # dense bounds are a point interval on every route
        assert counts.flops_lo == counts.flops_hi

    def test_tp_fast_path(self):
        policy = dataclasses.replace(tpu_default(0.8), tp_shards=2)
        rep = Report("t")
        savings.audit_dense_site(rep, "site", 64, 128, 256, policy)
        assert not rep.errors(), [f.message for f in rep.errors()]


class TestLmAudit:
    def test_reduced_decoder_no_errors(self):
        cfg = get_config("qwen2.5-3b").reduced()
        rep = savings.audit_lm(cfg, tpu_default(0.8), batch=2, seq=16)
        assert not rep.errors(), [f.message for f in rep.errors()]

    def test_iter_dense_shapes_families(self):
        from repro.models import transformer

        moe = get_config("kimi-k2-1t-a32b").reduced()
        sites = {s for s, *_ in transformer.iter_dense_shapes(moe, 2, 16)}
        assert any("moe/gate" in s for s in sites)
        assert any("moe/shared/up" in s for s in sites)
        encdec = get_config("whisper-large-v3").reduced()
        sites = {s for s, *_ in transformer.iter_dense_shapes(encdec, 2, 16)}
        assert any(s.startswith("enc/") for s in sites)
        assert any("/cross/" in s for s in sites)

    def test_lm_site_flops_rows(self):
        cfg = get_config("qwen2.5-3b").reduced()
        rows = savings.lm_site_flops(cfg, tpu_default(0.8), batch=2, seq=16)
        assert rows
        m = 2 * 16
        for site, count, fwd, lo, hi in rows:
            assert count >= 1 and lo <= hi
            if site.endswith("attn/q"):
                d = cfg.d_model
                assert fwd == 2 * m * d * (cfg.n_heads * cfg.head_dim)


# ----------------------------------------------------------------------
# seeded regressions: each lint must catch its plant
# ----------------------------------------------------------------------


class TestSeededRegressions:
    def test_planted_f32_upcast_is_caught(self, monkeypatch):
        policy = dataclasses.replace(tpu_default(0.8), bwd_dtype="bfloat16")
        rep = Report("clean")
        savings.audit_dense_site(
            rep, "site", 64, 128, 256, policy, dtype="bfloat16"
        )
        assert not rep.errors()

        monkeypatch.setattr(backward, "_acc_dtype", lambda p: jnp.float32)
        savings.clear_cache()
        rep = Report("seeded")
        savings.audit_dense_site(
            rep, "site", 64, 128, 256, policy, dtype="bfloat16"
        )
        assert any(f.check == "dtype" for f in rep.errors())

    def test_planted_host_callback_is_caught(self):
        def fn(x):
            jax.debug.callback(lambda a: None, x)
            return x * 2

        closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), "float32"))
        counts = jaxpr_walk.count(closed, name="t")
        rep = Report("t")
        lint_step_counts(rep, "t", counts)
        assert any(f.check == "transfer" for f in rep.errors())

    def test_clean_step_has_no_callback_errors(self):
        closed = jax.make_jaxpr(lambda x: x * 2)(
            jax.ShapeDtypeStruct((4,), "float32")
        )
        counts = jaxpr_walk.count(closed, name="t")
        rep = Report("t")
        lint_step_counts(rep, "t", counts)
        assert not rep.errors()

    def test_oob_index_map_is_caught(self):
        dw_spec, _, idx = pallas_check.conv_fused_site_specs(
            2, 8, 8, 32, 64, 3,
            dataclasses.replace(
                tpu_default(0.8), use_pallas=True, block_size=32
            ),
        )
        info = dw_spec.in_specs[0]
        bad = dataclasses.replace(
            info, index_map=lambda *a: tuple(10**6 for _ in info.block_shape)
        )
        bad_spec = dataclasses.replace(
            dw_spec, in_specs=(bad,) + dw_spec.in_specs[1:]
        )
        rep = Report("t")
        pallas_check.check_in_bounds(rep, bad_spec, prefetch_candidates=(idx,))
        assert any(f.check == "pallas" for f in rep.errors())

    def test_ragged_operand_is_caught(self):
        info = pallas_check.BlockSpecInfo(
            "x", (100,), (64,), lambda i: (i,)
        )
        spec = pallas_check.KernelSpec("k", (2,), (info,), ())
        rep = Report("t")
        pallas_check.check_divisibility(rep, spec)
        assert rep.errors()

    def test_vmem_over_budget_is_caught(self):
        big = pallas_check.BlockSpecInfo(
            "x", (4096, 4096), (4096, 4096), lambda i: (0, 0)
        )
        spec = pallas_check.KernelSpec("k", (1,), (big,), ())
        rep = Report("t")
        pallas_check.check_vmem(rep, spec, platform="tpu")
        assert rep.errors()


# ----------------------------------------------------------------------
# Pallas traffic cross-check on a real fused site
# ----------------------------------------------------------------------


class TestPallasTraffic:
    def test_fused_conv_traffic_matches_bytes_model(self):
        # the fused conv kernels, checked on their own: no engine route
        # runs them (the site audits as XLA's gathered conv alone)
        pol = dataclasses.replace(
            tpu_default(0.8), use_pallas=True, block_size=32
        )
        counts = savings.audit_conv_site(Report("t"), "site", 2, 8, 8, 32, 64, 3, pol)
        assert {c.prim for c in counts.contractions} == {"conv_general_dilated"}
        rep = Report("t")
        pallas_check.check_conv_fused_site(rep, "site", 2, 8, 8, 32, 64, 3, pol)
        assert not rep.errors(), [f.message for f in rep.errors()]

    def test_resnet18_fused_route_leaves_sites_that_keep_every_channel(self):
        # at 0.8 in 128-channel blocks no ResNet-18 site runs a Pallas
        # kernel: the ten that drop a block audit as XLA's gathered conv
        # at their kept width, the ten that keep every channel as dense
        from repro.models import resnet

        pol = dataclasses.replace(tpu_default(0.8), use_pallas=True)
        gathered = []
        for site, c_in, c_out, k, h, w in resnet.iter_conv_shapes("resnet18", (3, 32, 32)):
            rep = Report("t")
            counts = savings.audit_conv_site(rep, site, 2, h, w, c_in, c_out, k, pol)
            assert not rep.errors(), [f.message for f in rep.errors()]
            assert {c.prim for c in counts.contractions} == {"conv_general_dilated"}
            dense = ftab.conv_backward_contraction_bounds(2, h, w, c_in, c_out, k, DENSE)
            if (counts.flops_lo, counts.flops_hi) != dense:
                gathered.append(c_out)
        assert sorted(set(gathered)) == [256, 512]
        assert len(gathered) == 10

    def test_paged_attention_geometry(self):
        rep = Report("t")
        pallas_check.check_paged_attention_site(
            rep, b=2, s=8, h=4, d=16, n_pages=8, bs_pg=16, kvh=2, nb=4
        )
        assert not rep.errors(), [f.message for f in rep.errors()]


# ----------------------------------------------------------------------
# retrace budgets
# ----------------------------------------------------------------------


class TestRetrace:
    def _program(self):
        return PolicyProgram(
            rules=PolicyRules.single(tpu_default(0.8)),
            schedule=make_schedule("epoch_bar", target=0.8),
        )

    def test_train_within_budget(self):
        program = self._program()
        sites = ["layer_0/mlp/up", "layer_0/mlp/down"]
        tables = retrace.train_tables(program, sites)
        assert len(tables) <= len(program.schedule.rate_buckets)
        rep = Report("t")
        retrace.check_train_retrace(rep, program, sites)
        assert not rep.errors()

    def test_train_over_budget_fails(self):
        rep = Report("t")
        retrace.check_train_retrace(
            rep, self._program(), ["layer_0/mlp/up"], budget=0
        )
        assert rep.errors()

    def test_serve_executables_and_budget(self):
        from repro.serve.scheduler import ServeConfig

        cfg = get_config("qwen2.5-3b").reduced()
        serve_cfg = ServeConfig(
            max_slots=2, max_seq=64, prefill_chunk=8, spec_k=2
        )
        per_fn = retrace.serve_executables(cfg, serve_cfg)
        assert per_fn["_step_fn"] == len(serve_cfg.widths)
        assert per_fn["_draft_step_fn"] == 2  # catch-up + width-1 propose
        rep = Report("t")
        retrace.check_serve_retrace(rep, cfg, serve_cfg)
        assert not rep.errors()
        rep = Report("t")
        retrace.check_serve_retrace(rep, cfg, serve_cfg, budget=1)
        assert rep.errors()

    def test_serve_encdec_adds_encode_planes(self):
        from repro.serve.scheduler import ServeConfig

        cfg = get_config("whisper-large-v3").reduced()
        per_fn = retrace.serve_executables(
            cfg, ServeConfig(max_slots=2, max_seq=64, prefill_chunk=8,
                             spec_k=2)
        )
        assert per_fn["_encode"] == 1 and per_fn["_draft_encode"] == 1


# ----------------------------------------------------------------------
# the analyze CLI end to end (reports, exit code)
# ----------------------------------------------------------------------


class TestAnalyzeCli:
    def test_conv_model_clean(self, tmp_path):
        from repro.launch import analyze

        out = tmp_path / "r.json"
        rc = analyze.main([
            "--model", "resnet18", "--image", "3,8,8", "--batch", "2",
            "--use-pallas", "--block-size", "32", "--json", str(out),
        ])
        assert rc == 0
        assert out.exists()

    def test_lm_arch_clean(self):
        from repro.launch import analyze

        rc = analyze.main([
            "--arch", "qwen2.5-3b", "--reduced", "--seq-len", "16",
            "--global-batch", "2",
        ])
        assert rc == 0


# ----------------------------------------------------------------------
# docs / CLI static checker
# ----------------------------------------------------------------------


class TestCheckDocs:
    @pytest.fixture()
    def cd(self):
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import check_docs

        yield check_docs
        sys.path.pop(0)

    def test_real_docs_are_clean(self, cd):
        assert cd.main() == 0

    def test_unknown_flag_fails(self, cd):
        bad = "Run `python -m repro.launch.train --arch x --no-such-flag`"
        fails = cd.check_cli_flags(bad, "t.md")
        assert fails and "--no-such-flag" in fails[0]

    def test_continuation_lines_are_joined(self, cd):
        bad = (
            "```\npython -m repro.launch.serve --arch q \\\n"
            "  --bogus-flag 3\n```"
        )
        fails = cd.check_cli_flags(bad, "t.md")
        assert fails and "--bogus-flag" in fails[0]

    def test_known_flags_pass(self, cd):
        ok = (
            "`python -m repro.launch.serve --arch qwen2.5-3b --spec-k 2 "
            "--stream`"
        )
        assert cd.check_cli_flags(ok, "t.md") == []

    def test_missing_script_fails(self, cd):
        fails = cd.check_cli_flags(
            "`python -m repro.launch.nonexistent --x`", "t.md"
        )
        assert fails

    def test_out_of_repo_commands_ignored(self, cd):
        assert cd.check_cli_flags("`python -m pytest -x --tb=short`", "t.md") == []


# ----------------------------------------------------------------------
# roofline --lm-sites rows
# ----------------------------------------------------------------------


class TestRooflineLmSites:
    def test_rows_and_total(self):
        from benchmarks import roofline

        rows = roofline.lm_site_rows("qwen2.5-3b", "train_tight")
        assert rows[-1]["kind"] == "lm_site_total"
        total = rows[-1]
        per_site = [r for r in rows if r["kind"] == "lm_site"]
        assert per_site
        assert total["fwd_flops"] == sum(
            r["count"] * r["fwd_flops"] for r in per_site
        )
        assert total["bwd_flops_lo"] <= total["bwd_flops_hi"]
        assert 0 < total["ratio_vs_6nd"] < 1.5
