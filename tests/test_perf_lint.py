"""ATX6xx performance lint (`analysis/roofline.py`, `analysis/rules_perf.py`,
`analysis/perf_budget.py`, `ops/autotune.py`) — every rule fires on its
seeded defect and stays quiet on the clean configurations, the budget
ratchet fails on an injected regression, and the autotune cache
persists/overrides correctly. Runs on the 8-device CPU simulation
(conftest) under jax 0.4.37.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import analysis
from accelerate_tpu.analysis import Severity, perf_budget, roofline
from accelerate_tpu.analysis.findings import Finding, Report
from accelerate_tpu.analysis.rules_collectives import (
    parse_collectives,
    parse_collectives_detailed,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PERF_RULES = {"ATX602", "ATX603", "ATX604", "ATX605"}


def sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def ids(report, min_severity=Severity.INFO):
    return {f.rule_id for f in report.filter(min_severity)}


def finding(report, rule_id):
    hits = [f for f in report.findings if f.rule_id == rule_id]
    assert hits, f"{rule_id} did not fire: {[f.rule_id for f in report.findings]}"
    return hits[0]


def ctx_with_hlo(text, **options):
    """A LintContext whose compiled HLO is the given text — the seeded-HLO
    harness for rules whose defect the CPU backend cannot produce (async
    collectives lower synchronously here)."""
    ctx = analysis.LintContext(fn=lambda: None, options=options)
    ctx._compiled_text = text
    return ctx


V5E = roofline.CHIP_SPECS["v5e"]


# ------------------------------------------------------------- chip specs
class TestChipSpecs:
    def test_known_generations_present(self):
        for name in ("v4", "v5e", "v5p", "v6e", "cpu"):
            spec = roofline.CHIP_SPECS[name]
            assert spec.name == name
            assert spec.peak_flops["bf16"] > 0
            assert spec.hbm_bytes_per_sec > 0

    def test_resolve_by_name_and_device_kind(self):
        assert roofline.chip_spec_for("v5p").name == "v5p"
        assert roofline.chip_spec_for("TPU v5 lite").name == "v5e"
        assert roofline.chip_spec_for("TPU v4").name == "v4"
        # container auto-detect: no TPU attached -> cpu stand-in
        assert roofline.chip_spec_for().name == "cpu"

    @pytest.mark.parametrize(
        "chip",
        ["TPU v9 ultra", types.SimpleNamespace(device_kind="TPU v9 ultra", platform="tpu")],
        ids=["kind-string", "device"],
    )
    def test_unknown_accelerator_raises(self, chip):
        # Never the cpu stand-in's numbers under an accelerator's name.
        with pytest.raises(ValueError, match="TPU v9 ultra"):
            roofline.chip_spec_for(chip)

    def test_cpu_stand_in_only_for_cpu(self):
        cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
        assert roofline.chip_spec_for(cpu).name == "cpu"
        assert roofline.chip_spec_for("cpu").name == "cpu"
        assert roofline.chip_spec_for(jax.devices()[0]).name == "cpu"

    def test_dtype_packing(self):
        assert V5E.native_sublane("f32") == 8
        assert V5E.native_sublane("bf16") == 16
        assert V5E.native_sublane("s8") == 32
        assert V5E.peak_for("bf16") > V5E.peak_for("f32")


# -------------------------------------------------------------- HLO parse
class TestRooflineParser:
    def test_dot_flops_exact_from_compiled_hlo(self):
        text = (
            jax.jit(lambda a, b: a @ b)
            .lower(sds(256, 512), sds(512, 128))
            .compile()
            .as_text()
        )
        res = roofline.analyze_hlo(text, V5E)
        assert res.mxu_flops == 2 * 256 * 128 * 512
        assert len(res.dots) == 1
        d = res.dots[0]
        assert (d.m, d.n, d.k) == (256, 128, 512)
        assert d.intensity > 0

    def test_scan_trip_count_multiplies_loop_work(self):
        def f(x, w):
            y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=16)
            return y

        text = jax.jit(f).lower(sds(64, 64), sds(64, 64)).compile().as_text()
        res = roofline.analyze_hlo(text, V5E)
        assert res.mxu_flops == 16 * 2 * 64 * 64 * 64

    def test_while_trip_count_from_condition_pattern(self):
        text = """
%cond (arg: (s32[], f32[8])) -> pred[] {
  %arg = (s32[], f32[8]) parameter(0)
  %gte = s32[] get-tuple-element((s32[], f32[8]) %arg), index=0
  %k = s32[] constant(24)
  ROOT %cmp = pred[] compare(s32[] %gte, s32[] %k), direction=LT
}
"""
        comps = roofline.parse_hlo_module(text)
        assert roofline.while_trip_count(comps, "cond") == 24

    def test_step_time_bound_and_mfu_ceiling(self):
        text = (
            jax.jit(lambda a, b: a @ b)
            .lower(sds(512, 512), sds(512, 512))
            .compile()
            .as_text()
        )
        res = roofline.analyze_hlo(text, V5E)
        assert res.step_time_lower_bound_s > 0
        assert 0 < res.static_mfu_bound <= 1.0
        assert res.bound_category in ("mxu", "vector", "hbm", "collective")


# ---------------------------------------------- collectives parser upgrade
_ASYNC_HLO = """
ENTRY %main (p0: f32[2048,1024]) -> f32[2048,1024] {
  %p0 = f32[2048,1024]{1,0} parameter(0)
  %ags = (f32[2048,1024]{1,0}, f32[2048,1024]{1,0}) all-gather-start(f32[2048,1024]{1,0} %p0), replica_groups={{0,1}}, dimensions={0}
  ROOT %agd = f32[2048,1024]{1,0} all-gather-done((f32[2048,1024]{1,0}, f32[2048,1024]{1,0}) %ags)
}
"""


class TestDetailedCollectiveParser:
    def test_variants_and_positions(self):
        sites = parse_collectives_detailed(_ASYNC_HLO)
        assert [(s.op, s.variant) for s in sites] == [
            ("all-gather", "start"),
            ("all-gather", "done"),
        ]
        assert sites[0].name == "ags"
        assert sites[0].line < sites[1].line
        assert sites[0].bytes == 2 * 2048 * 1024 * 4  # start tuple: in + out

    def test_byte_summary_skips_done_halves(self):
        # the public parser's contract: one byte entry per collective
        assert parse_collectives(_ASYNC_HLO) == [
            ("all-gather", 2 * 2048 * 1024 * 4)
        ]

    def test_sync_collective_unchanged(self):
        text = "  %ar = f32[16,512]{1,0} all-reduce(f32[16,512]{1,0} %x)"
        (site,) = parse_collectives_detailed(text)
        assert (site.op, site.variant, site.bytes) == (
            "all-reduce", "sync", 16 * 512 * 4
        )


# ------------------------------------------------------------------ ATX601
class TestATX601Roofline:
    def test_fires_with_machine_readable_table(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(512, 512), sds(512, 512),
            roofline_chip="v5e",
        )
        f = finding(report, "ATX601")
        assert f.severity == Severity.INFO
        data = f.data
        assert data["chip"] == "v5e"
        assert 0 < data["static_mfu_bound"] <= 1.0
        assert data["step_time_lower_bound_ms"] > 0
        assert {row["category"] for row in data["categories"]} == {
            "mxu", "vector", "hbm", "collective"
        }
        assert data["top_ops"] and data["top_ops"][0]["flops"] == 2 * 512 ** 3
        # the ATX601-owned budgeted series are always present (the memory
        # series ride on ATX701/ATX706 instead)
        for key, rule_id in perf_budget._SERIES_RULES.items():
            if rule_id == "ATX601":
                assert key in data
        # and survive the --json surface
        assert "data" in f.to_dict()

    def test_json_roundtrip_of_report(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 256), sds(256, 256)
        )
        blob = json.loads(report.to_json())
        atx601 = [f for f in blob["findings"] if f["rule_id"] == "ATX601"]
        assert atx601 and "static_mfu_bound" in atx601[0]["data"]


# ------------------------------------------------------------------ ATX602
def _pair_hlo(between: str) -> str:
    return f"""
ENTRY %main (p0: f32[2048,1024]) -> f32[2048,1024] {{
  %p0 = f32[2048,1024]{{1,0}} parameter(0)
  %w = f32[4096,4096]{{1,0}} parameter(1)
  %ags = (f32[2048,1024]{{1,0}}, f32[2048,1024]{{1,0}}) all-gather-start(f32[2048,1024]{{1,0}} %p0), replica_groups={{{{0,1}}}}, dimensions={{0}}
{between}
  ROOT %agd = f32[2048,1024]{{1,0}} all-gather-done((f32[2048,1024]{{1,0}}, f32[2048,1024]{{1,0}}) %ags)
}}
"""


_BIG_DOT = (
    "  %dot.1 = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %w, "
    "f32[4096,4096]{1,0} %w), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}"
)


class TestATX602ExposedCollective:
    def test_seeded_nonoverlapped_all_gather_fires(self):
        from accelerate_tpu.analysis import rules_perf

        ctx = ctx_with_hlo(_pair_hlo(""), roofline_chip="v5e")
        findings = list(rules_perf.atx602_exposed_collective(ctx))
        assert len(findings) == 1
        f = findings[0]
        assert f.severity == Severity.WARNING
        assert f.data["bytes"] == 2 * 2048 * 1024 * 4
        assert f.data["exposed_ms"] > 0
        assert f.data["overlap_compute_ms"] == 0

    def test_overlapped_pair_is_quiet(self):
        from accelerate_tpu.analysis import rules_perf

        # a 137-GFLOP dot between start and done hides the 0.08 ms wire
        ctx = ctx_with_hlo(_pair_hlo(_BIG_DOT), roofline_chip="v5e")
        assert list(rules_perf.atx602_exposed_collective(ctx)) == []

    def test_below_byte_floor_is_quiet(self):
        from accelerate_tpu.analysis import rules_perf

        ctx = ctx_with_hlo(
            _pair_hlo(""), roofline_chip="v5e",
            exposed_min_bytes=1 << 30,
        )
        assert list(rules_perf.atx602_exposed_collective(ctx)) == []

    def test_sync_collectives_never_judged(self):
        exposed = roofline.find_exposed_collectives(
            "  %ar = f32[4096,4096]{1,0} all-reduce(f32[4096,4096]{1,0} %x)",
            V5E,
            min_bytes=0,
        )
        assert exposed == []


# ------------------------------------------------------------------ ATX603
class TestATX603TilingWaste:
    OPTS = dict(roofline_chip="v5e", tiling_min_waste_flops=1e3)

    def test_odd_contraction_dim_fires(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 513), sds(513, 256), **self.OPTS
        )
        f = finding(report, "ATX603")
        assert f.severity == Severity.WARNING
        # k=513 pads to 640 on the 128-lane MXU: ~19.8% dead work
        assert f.data["dims"]["k"] == 513
        assert 0.15 < f.data["waste_fraction"] < 0.25
        assert f.data["padded_flops"] > f.data["flops"]

    def test_tile_aligned_dims_quiet(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 512), sds(512, 256), **self.OPTS
        )
        assert "ATX603" not in ids(report)

    def test_subtile_dims_are_model_scale_not_bugs(self):
        # 64 < the 128 lane tile: padding is intrinsic to the model size,
        # not a tiling mistake — must not flag (keeps BERT-tiny quiet).
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(64, 64), sds(64, 64), **self.OPTS
        )
        assert "ATX603" not in ids(report)
        f = finding(report, "ATX601")
        assert f.data["padding_waste_fraction"] == 0.0


# ------------------------------------------------------------------ ATX604
class TestATX604PrecisionFallback:
    def test_upcast_before_hot_dot_fires(self):
        def f(a, b):
            return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

        report = analysis.lint_step(
            f, sds(256, 256, dtype=jnp.bfloat16),
            sds(256, 256, dtype=jnp.bfloat16), roofline_chip="v5e",
        )
        f601 = finding(report, "ATX604")
        assert f601.severity == Severity.WARNING
        assert f601.data["upcast_from"] == "bf16"
        assert f601.data["result_dtype"] == "f32"
        assert f601.data["share_of_mxu_flops"] == pytest.approx(1.0)

    def test_native_f32_dot_quiet(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 256), sds(256, 256),
            roofline_chip="v5e",
        )
        assert "ATX604" not in ids(report)


# ------------------------------------------------------------------ ATX605
def _fusion_chain_hlo(dim: int) -> str:
    shape = f"f32[{dim},{dim}]"
    return f"""
%fused_computation.1 (param_0.1: {shape}) -> {shape} {{
  %param_0.1 = {shape}{{1,0}} parameter(0)
  ROOT %mul.1 = {shape}{{1,0}} multiply({shape}{{1,0}} %param_0.1, {shape}{{1,0}} %param_0.1)
}}

%fused_computation.2 (param_0.2: {shape}) -> {shape} {{
  %param_0.2 = {shape}{{1,0}} parameter(0)
  ROOT %add.1 = {shape}{{1,0}} add({shape}{{1,0}} %param_0.2, {shape}{{1,0}} %param_0.2)
}}

ENTRY %main (p0: {shape}) -> {shape} {{
  %p0 = {shape}{{1,0}} parameter(0)
  %fusion.1 = {shape}{{1,0}} fusion({shape}{{1,0}} %p0), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.2 = {shape}{{1,0}} fusion({shape}{{1,0}} %fusion.1), kind=kLoop, calls=%fused_computation.2
}}
"""


class TestATX605FusionBreak:
    def test_large_materialized_intermediate_fires(self):
        from accelerate_tpu.analysis import rules_perf

        ctx = ctx_with_hlo(_fusion_chain_hlo(4096))  # 64 MiB intermediate
        findings = list(rules_perf.atx605_fusion_break(ctx))
        assert len(findings) == 1
        f = findings[0]
        assert f.data["producer"] == "fusion.1"
        assert f.data["consumer"] == "fusion.2"
        assert f.data["buffer_bytes"] == 4096 * 4096 * 4
        assert f.data["extra_hbm_bytes"] == 2 * 4096 * 4096 * 4

    def test_small_intermediate_quiet(self):
        from accelerate_tpu.analysis import rules_perf

        ctx = ctx_with_hlo(_fusion_chain_hlo(256))  # 256 KiB
        assert list(rules_perf.atx605_fusion_break(ctx)) == []

    def test_multi_consumer_quiet(self):
        # a buffer two fusions read is a legitimate materialization point
        text = _fusion_chain_hlo(4096).replace(
            "ROOT %fusion.2 = f32[4096,4096]{1,0} fusion(f32[4096,4096]{1,0} %fusion.1), kind=kLoop, calls=%fused_computation.2",
            "%fusion.2 = f32[4096,4096]{1,0} fusion(f32[4096,4096]{1,0} %fusion.1), kind=kLoop, calls=%fused_computation.2\n"
            "  ROOT %add.9 = f32[4096,4096]{1,0} add(f32[4096,4096]{1,0} %fusion.1, f32[4096,4096]{1,0} %fusion.2)",
        )
        assert roofline.find_fusion_breaks(text, min_bytes=1 << 20) == []


# ------------------------------------------------------- clean scenarios
class TestCleanScenarios:
    def test_nlp_example_has_roofline_but_no_perf_warnings(self):
        from accelerate_tpu.commands.lint import SCENARIOS

        _, report = SCENARIOS["nlp_example"](roofline_chip="v5e")
        got = ids(report)
        assert "ATX601" in got
        assert not (got & PERF_RULES), report.findings

    def test_lint_training_grows_the_family_automatically(self):
        from accelerate_tpu.commands.lint import SCENARIOS

        _, report = SCENARIOS["nlp_example"]()
        series = perf_budget.extract_series(report)
        assert series is not None
        # train scenarios carry every series except the serving planner's
        assert set(series) == set(perf_budget.SERIES) - {"serve_static_max_slots"}


# ------------------------------------------------------------ budget gate
def _report_with_series(mfu=0.5, comms=0.0, waste=0.0):
    return Report(
        findings=[
            Finding(
                "ATX601", Severity.INFO, "v5e", "roofline", "",
                data={
                    "static_mfu_bound": mfu,
                    "exposed_comms_bytes": comms,
                    "padding_waste_fraction": waste,
                },
            )
        ]
    )


class TestBudgetRatchet:
    def test_roundtrip_and_hold(self, tmp_path):
        path = str(tmp_path / "budgets.json")
        series = perf_budget.extract_series(_report_with_series())
        perf_budget.write_budgets(path, {"scn": series})
        budgets = perf_budget.load_budgets(path)
        assert budgets["scn"]["static_mfu_bound"] == 0.5
        assert perf_budget.check_budgets(budgets, {"scn": series}) == []

    def test_injected_regressions_fail(self):
        budgets = {"scn": perf_budget.extract_series(_report_with_series())}
        worse_mfu = perf_budget.extract_series(_report_with_series(mfu=0.4))
        assert any(
            "static_mfu_bound" in p
            for p in perf_budget.check_budgets(budgets, {"scn": worse_mfu})
        )
        worse_comms = perf_budget.extract_series(
            _report_with_series(comms=10 << 20)
        )
        assert any(
            "exposed_comms_bytes" in p
            for p in perf_budget.check_budgets(budgets, {"scn": worse_comms})
        )
        worse_waste = perf_budget.extract_series(_report_with_series(waste=0.2))
        assert any(
            "padding_waste_fraction" in p
            for p in perf_budget.check_budgets(budgets, {"scn": worse_waste})
        )

    def test_within_tolerance_holds(self):
        budgets = {"scn": perf_budget.extract_series(_report_with_series())}
        wobble = perf_budget.extract_series(_report_with_series(mfu=0.495))
        assert perf_budget.check_budgets(budgets, {"scn": wobble}) == []

    def test_budgeted_scenario_that_stopped_compiling_fails(self):
        budgets = {"scn": {"static_mfu_bound": 0.5}}
        assert perf_budget.check_budgets(budgets, {"scn": None})

    def test_scenario_not_in_this_run_is_skipped(self):
        budgets = {"other": {"static_mfu_bound": 0.5}}
        assert perf_budget.check_budgets(budgets, {"scn": None}) == []

    def test_committed_budgets_file_is_valid(self):
        budgets = perf_budget.load_budgets(os.path.join(REPO, "perf", "budgets.json"))
        assert set(budgets) >= {
            "nlp_example", "lm_example", "cv_example", "llama2b", "serving",
        }
        for series in budgets.values():
            assert series and set(series) <= set(perf_budget.SERIES)
        assert "peak_hbm_mib" in budgets["llama2b"]
        assert "serve_static_max_slots" in budgets["serving"]


# ---------------------------------------------------------- autotune cache
class TestAutotuneCache:
    def test_persist_and_reload(self, tmp_path, monkeypatch):
        from accelerate_tpu.ops import autotune

        monkeypatch.setenv("ATX_AUTOTUNE_DIR", str(tmp_path))
        cache = autotune.AutotuneCache(chip="v5e")
        assert autotune.cached_pick_block("flash", 4096, cache=cache) == 512
        disk = json.load(open(tmp_path / "v5e.json"))
        assert disk["blocks"]["flash|4096|any"] == 512
        # a fresh cache (new process) reads the persisted entry
        fresh = autotune.AutotuneCache(chip="v5e")
        assert fresh.get("flash", (4096,), "any") == 512

    def test_env_override_wins(self, monkeypatch):
        from accelerate_tpu.ops import autotune

        cache = autotune.AutotuneCache(chip="v5e", directory="")
        cache.put("flash", (4096,), "any", 512)
        monkeypatch.setenv("ATX_BLOCK_FLASH", "128")
        assert cache.get("flash", (4096,), "any") == 128
        assert autotune.cached_pick_block("flash", 4096, cache=cache) == 128

    def test_stale_non_dividing_entry_ignored(self):
        from accelerate_tpu.ops import autotune

        cache = autotune.AutotuneCache(chip="v5e", directory="")
        cache.put("flash", (4000,), "any", 3000)  # does not divide
        assert autotune.cached_pick_block("flash", 4000, cache=cache) == 32

    def test_in_memory_without_dir(self, monkeypatch, tmp_path):
        from accelerate_tpu.ops import autotune

        monkeypatch.delenv("ATX_AUTOTUNE_DIR", raising=False)
        cache = autotune.AutotuneCache(chip="v5e")
        assert cache.path is None
        cache.put("flash", (1024,), "bfloat16", 256)
        assert cache.get("flash", (1024,), "bfloat16") == 256
        assert list(tmp_path.iterdir()) == []

    def test_kernel_tier_pick_block_still_divides(self):
        # the wired kernels rely on divide-exactly semantics
        from accelerate_tpu.native.pallas import decode_attention

        blk = decode_attention.pick_block(4096, 2048)  # a cache of 4096 rows of 2 KB
        assert blk is not None and 4096 % blk == 0

    def test_corrupt_cache_file_is_empty_cache(self, tmp_path, monkeypatch):
        from accelerate_tpu.ops import autotune

        (tmp_path / "v5e.json").write_text("{torn")
        monkeypatch.setenv("ATX_AUTOTUNE_DIR", str(tmp_path))
        cache = autotune.AutotuneCache(chip="v5e")
        assert cache.get("flash", (4096,), "any") is None
