"""ATX7xx static memory lint (`analysis/memory.py`, `analysis/rules_memory.py`,
`analysis/capacity.py`) — the HBM-timeline sweep agrees with the
executable's own `memory_analysis()` totals, every rule fires on its
seeded defect and stays quiet on the clean pair, the serving capacity
planner's arithmetic and engine-init guard behave, and the budget ratchet
fails on an injected `peak_hbm_mib` / `serve_static_max_slots`
regression. Runs on the 8-device CPU simulation (conftest) under
jax 0.4.37.
"""

import json
import warnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import analysis
from accelerate_tpu.analysis import Severity, capacity, memory, perf_budget
from accelerate_tpu.analysis import rules_memory
from accelerate_tpu.analysis.findings import Finding, Report
from accelerate_tpu.state import AcceleratorState
from accelerate_tpu.utils.environment import patch_environment


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
    harness for timeline shapes the CPU backend will not schedule."""
    ctx = analysis.LintContext(fn=lambda: None, options=options)
    ctx._compiled_text = text
    return ctx


F32x256 = "f32[256,256]{1,0}"
KIB256 = 256 * 256 * 4  # one f32[256,256] buffer


# -------------------------------------------------- param-path classifier
class TestParamPathClassifier:
    def test_params_tokens(self):
        assert memory.classify_param_path("state['params']['wq']") == "params"
        assert memory.classify_param_path("weights.layer0.kernel") == "params"

    def test_opt_state_wins_over_nested_params(self):
        # optimizer moments mirror the param tree — opt tokens must win
        assert memory.classify_param_path("opt_state.mu['params']['wq']") == "opt_state"
        assert memory.classify_param_path("state['grads']['wk']") == "opt_state"
        assert memory.classify_param_path("exp_avg_sq['dense']") == "opt_state"

    def test_kv_wins_over_everything(self):
        assert memory.classify_param_path("cache['k_cache']") == "kv"
        assert memory.classify_param_path("kv_cache[3]['params']") == "kv"

    def test_unrecognized_is_inputs(self):
        assert memory.classify_param_path("batch['input_ids']") == "inputs"
        assert memory.classify_param_path("") == "inputs"


class TestAliasParsing:
    def test_module_header_aliases(self):
        text = (
            "HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
            "{1}: (3, {}, must-alias) }, entry_computation_layout={...}"
        )
        assert memory.parse_input_output_aliases(text) == [0, 3]

    def test_absent_header_is_empty(self):
        assert memory.parse_input_output_aliases("HloModule m\n") == []


# ------------------------------------------------------ timeline mechanics
def _chain_hlo(header_extra=""):
    return f"""HloModule m{header_extra}, is_scheduled=true

ENTRY %main.1 (p0: f32[256,256], p1: f32[256,256]) -> f32[256,256] {{
  %p0 = {F32x256} parameter(0)
  %p1 = {F32x256} parameter(1)
  %a = {F32x256} add({F32x256} %p0, {F32x256} %p1)
  %b = {F32x256} multiply({F32x256} %a, {F32x256} %a)
  ROOT %c = {F32x256} add({F32x256} %b, {F32x256} %p0)
}}
"""


class TestTimelineMechanics:
    def test_liveness_sweep_on_a_chain(self):
        t = memory.build_timeline(_chain_hlo())
        assert t.n_instructions == 5
        assert len(t.series) == 5
        # params (2) live throughout; `a` and `b` overlap at the multiply
        assert t.peak_bytes == 4 * KIB256
        assert t.peak_index == 3 and "multiply" in t.peak_instr
        assert t.argument_bytes == 2 * KIB256
        assert t.output_bytes == KIB256
        assert t.alias_bytes == 0
        assert t.output_signatures == [("f32", (256, 256))]
        a = next(b for b in t.buffers if b.name == "a")
        assert (a.def_index, a.first_use, a.last_use) == (2, 3, 3)

    def test_params_live_for_whole_program(self):
        t = memory.build_timeline(_chain_hlo())
        for b in t.buffers:
            if b.op == "parameter":
                assert b.def_index == 0 and b.last_use == t.n_instructions

    def test_donation_credits_output_producer(self):
        text = f"""HloModule m, input_output_alias={{ {{}}: (0, {{}}, may-alias) }}

ENTRY %main.1 (p0: f32[256,256]) -> f32[256,256] {{
  %p0 = {F32x256} parameter(0)
  ROOT %c = {F32x256} add({F32x256} %p0, {F32x256} %p0)
}}
"""
        undonated = memory.build_timeline(text.replace(
            ", input_output_alias={ {}: (0, {}, may-alias) }", ""))
        donated = memory.build_timeline(text)
        assert undonated.peak_bytes == 2 * KIB256
        assert donated.peak_bytes == KIB256  # output recycles p0's storage
        assert donated.alias_bytes == KIB256
        p0 = next(b for b in donated.buffers if b.op == "parameter")
        assert p0.donated
        c = next(b for b in donated.buffers if b.name == "c")
        assert c.bytes == 0 and c.is_output

    def test_param_op_name_metadata_categorizes(self):
        text = _chain_hlo().replace(
            "%p0 = f32[256,256]{1,0} parameter(0)",
            '%p0 = f32[256,256]{1,0} parameter(0), '
            'metadata={op_name="state[\'params\'][\'w\']"}',
        )
        t = memory.build_timeline(text)
        p0 = next(b for b in t.buffers if b.param_number == 0)
        assert p0.category == "params"
        assert t.categories_at_peak["params"] == KIB256

    def test_while_body_charged_at_the_call_site(self):
        text = """HloModule m

%body.1 (barg: (s32[], f32[256,256])) -> (s32[], f32[256,256]) {
  %barg = (s32[], f32[256,256]) parameter(0)
  %iv = s32[] get-tuple-element((s32[], f32[256,256]) %barg), index=0
  %one = s32[] constant(1)
  %niv = s32[] add(s32[] %iv, s32[] %one)
  %acc = f32[256,256]{1,0} get-tuple-element((s32[], f32[256,256]) %barg), index=1
  %big = f32[512,512]{1,0} broadcast(f32[256,256]{1,0} %acc), dimensions={0,1}
  %nacc = f32[256,256]{1,0} slice(f32[512,512]{1,0} %big), slice={[0:256], [0:256]}
  ROOT %btup = (s32[], f32[256,256]) tuple(s32[] %niv, f32[256,256]{1,0} %nacc)
}

%cond.1 (carg: (s32[], f32[256,256])) -> pred[] {
  %carg = (s32[], f32[256,256]) parameter(0)
  %civ = s32[] get-tuple-element((s32[], f32[256,256]) %carg), index=0
  %k = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %civ, s32[] %k), direction=LT
}

ENTRY %main.2 (p0: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,256]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[256,256]) tuple(s32[] %zero, f32[256,256]{1,0} %p0)
  %wh = (s32[], f32[256,256]) while((s32[], f32[256,256]) %init), condition=%cond.1, body=%body.1
  ROOT %out = f32[256,256]{1,0} get-tuple-element((s32[], f32[256,256]) %wh), index=1
}
"""
        t = memory.build_timeline(text)
        # the body's 1 MiB broadcast is resident while the loop runs
        assert "while" in t.peak_instr
        assert t.peak_bytes > 512 * 512 * 4
        assert t.categories_at_peak.get("activations", 0) >= 512 * 512 * 4

    def test_fusion_temps_collapse(self):
        text = """HloModule m

%fused.1 (fp: f32[64,64]) -> f32[64,64] {
  %fp = f32[64,64]{1,0} parameter(0)
  %huge = f32[2048,2048]{1,0} broadcast(f32[64,64]{1,0} %fp), dimensions={0,1}
  ROOT %fout = f32[64,64]{1,0} slice(f32[2048,2048]{1,0} %huge), slice={[0:64], [0:64]}
}

ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  ROOT %f = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %p0), kind=kLoop, calls=%fused.1
}
"""
        t = memory.build_timeline(text)
        # only the fusion's materialized output counts, not the 16 MiB temp
        assert t.peak_bytes == 2 * 64 * 64 * 4

    def test_downsampled_series_keeps_the_peak(self):
        lines = [
            "ENTRY %main.1 (p0: f32[256,256]) -> f32[256,256] {",
            f"  %p0 = {F32x256} parameter(0)",
            f"  %t0 = {F32x256} add({F32x256} %p0, {F32x256} %p0)",
        ]
        for i in range(1, 600):
            lines.append(
                f"  %t{i} = {F32x256} add({F32x256} %t{i - 1}, {F32x256} %t{i - 1})"
            )
        lines.append(
            f"  ROOT %t600 = {F32x256} add({F32x256} %t599, {F32x256} %t599)"
        )
        lines.append("}")
        t = memory.build_timeline("HloModule m\n\n" + "\n".join(lines) + "\n")
        ds = t.downsampled_series(max_points=256)
        assert len(ds) <= 257
        assert any(b == t.peak_bytes for _, b in ds)
        assert json.dumps(ds)  # the --json payload shape

    def test_unparseable_text_is_none(self):
        assert memory.build_timeline("not hlo at all") is None


# --------------------------------------- cross-check vs memory_analysis()
def _train_like_step(state, batch):
    w = state["params"]["w"]
    g = jnp.tanh(batch @ w).T @ batch
    return {"params": {"w": w - 0.1 * g}}, jnp.sum(g)


class TestTimelineVsMemoryAnalysis:
    def test_donated_step_totals_within_tolerance(self):
        compiled = (
            jax.jit(_train_like_step, donate_argnums=(0,))
            .lower({"params": {"w": sds(256, 256)}}, sds(128, 256))
            .compile()
        )
        t = memory.build_timeline(compiled.as_text())
        assert t is not None and t.peak_bytes > 0
        assert t.alias_bytes == 256 * 256 * 4
        cross = t.cross_check(compiled.memory_analysis())
        assert cross, "memory_analysis reported no totals to check against"
        # the acceptance bar: totals agree with the executable within 5%
        for key, err in cross.items():
            assert err < 0.05, (key, err, cross)

    def test_scan_program_builds_a_timeline(self):
        def loop(x):
            def body(c, _):
                return jnp.tanh(c @ c), None

            y, _ = jax.lax.scan(body, x, None, length=8)
            return y

        compiled = jax.jit(loop).lower(sds(128, 128)).compile()
        t = memory.build_timeline(compiled.as_text())
        assert t is not None
        assert t.peak_bytes >= 128 * 128 * 4
        assert len(t.series) == t.n_instructions


# ------------------------------------------------------------------ ATX701
class TestATX701PeakReport:
    def test_always_fires_with_timeline_payload(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 512), sds(512, 128),
            roofline_chip="v5e",
        )
        f = finding(report, "ATX701")
        assert f.severity == Severity.INFO
        assert f.data["peak_hbm_bytes"] > 0
        assert f.data["peak_hbm_mib"] == pytest.approx(
            f.data["peak_hbm_bytes"] / 2**20
        )
        assert f.data["hbm_capacity_bytes"] == 16 << 30  # v5e
        assert 0.0 < f.data["headroom_fraction"] < 1.0
        assert sum(f.data["categories_at_peak"].values()) == f.data["peak_hbm_bytes"]
        assert f.data["timeline"], "series missing from the --json payload"
        json.dumps(f.data)  # must survive `atx lint --json`

    def test_cross_check_rides_in_data(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 512), sds(512, 128),
            roofline_chip="v5e",
        )
        f = finding(report, "ATX701")
        assert f.data["memory_analysis"] is not None
        assert f.data["memory_analysis"]["argument"] > 0
        for key, err in f.data["cross_check"].items():
            assert err < 0.05, (key, err)


# ------------------------------------------------------------------ ATX702
class TestATX702OomAheadOfTime:
    def test_seeded_over_capacity_fires(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 512), sds(512, 128),
            roofline_chip="v5e", hbm_capacity_bytes=1024,
        )
        f = finding(report, "ATX702")
        assert f.severity == Severity.ERROR
        assert f.data["over_bytes"] == f.data["peak_hbm_bytes"] - 1024
        assert "exceeds" in f.message

    def test_clean_capacity_quiet(self):
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(256, 512), sds(512, 128),
            roofline_chip="v5e",
        )
        assert "ATX702" not in ids(report)


# ------------------------------------------------------------------ ATX703
def _liverange_hlo(gap_fillers):
    big = "f32[1024,1024]{1,0}"
    small = "f32[64,64]{1,0}"
    lines = [
        "ENTRY %main.1 (p0: f32[64,64]) -> f32[1024,1024] {",
        f"  %p0 = {small} parameter(0)",
        f"  %big = {big} broadcast({small} %p0), dimensions={{0,1}}",
        f"  %t0 = {small} add({small} %p0, {small} %p0)",
    ]
    for i in range(1, gap_fillers):
        lines.append(f"  %t{i} = {small} add({small} %t{i - 1}, {small} %t{i - 1})")
    lines.append(f"  ROOT %use = {big} multiply({big} %big, {big} %big)")
    lines.append("}")
    return "HloModule m\n\n" + "\n".join(lines) + "\n"


class TestATX703LiverangeWaste:
    OPTS = dict(liverange_gap_instrs=10, liverange_min_bytes=1 << 20)

    def test_seeded_idle_buffer_fires(self):
        ctx = ctx_with_hlo(_liverange_hlo(30), **self.OPTS)
        findings = list(rules_memory.atx703_liverange_waste(ctx))
        assert len(findings) == 1
        f = findings[0]
        assert f.severity == Severity.WARNING
        assert f.data["name"] == "big"
        assert f.data["bytes"] == 1024 * 1024 * 4
        assert f.data["def_index"] == 1
        assert f.data["idle_instructions"] == f.data["first_use"] - 1 >= 10

    def test_consumer_next_door_quiet(self):
        ctx = ctx_with_hlo(_liverange_hlo(3), **self.OPTS)
        assert list(rules_memory.atx703_liverange_waste(ctx)) == []

    def test_parameters_never_flagged(self):
        # params are caller-owned for the whole program by construction
        ctx = ctx_with_hlo(
            _liverange_hlo(30), liverange_gap_instrs=1, liverange_min_bytes=1,
        )
        assert all(
            f.data["op"] != "parameter"
            for f in rules_memory.atx703_liverange_waste(ctx)
        )


# ------------------------------------------------------------------ ATX704
class TestATX704DonationMissAtPeak:
    STATE = {"params": {"w": sds(512, 1024)}}  # 2 MiB of trainable state

    def test_undonated_state_at_peak_fires(self):
        report = analysis.lint_step(
            _train_like_step, {"params": {"w": sds(512, 512)}}, sds(128, 512),
            roofline_chip="v5e",
        )
        f = finding(report, "ATX704")
        assert f.severity == Severity.WARNING
        assert f.data["category"] == "params"
        assert f.data["bytes"] == 512 * 512 * 4
        assert f.data["shape"] == [512, 512]

    def test_donated_state_quiet(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # CPU donation chatter
            report = analysis.lint_step(
                _train_like_step, {"params": {"w": sds(512, 512)}},
                sds(128, 512), donate_argnums=(0,), roofline_chip="v5e",
            )
        assert "ATX704" not in ids(report)

    def test_plain_inputs_never_flagged(self):
        # batch args categorize as inputs — no donation advice for data
        report = analysis.lint_step(
            lambda a, b: a @ b, sds(512, 512), sds(512, 512),
            roofline_chip="v5e", donation_peak_min_bytes=1,
        )
        assert "ATX704" not in ids(report)


# ------------------------------------------------------------------ ATX705
def _temp_blowup_hlo(n_copies):
    big = "f32[1024,1024]{1,0}"
    lines = [
        "ENTRY %main.1 (p0: f32[1024,1024]) -> (f32[1024,1024]) {",
        f"  %p0 = {big} parameter(0)",
    ]
    for i in range(n_copies):
        lines.append(f"  %c{i} = {big} copy({big} %p0)")
    operands = ", ".join(f"{big} %c{i}" for i in range(n_copies))
    types = ", ".join(["f32[1024,1024]"] * n_copies)
    lines.append(f"  ROOT %tup = ({types}) tuple({operands})")
    lines.append("}")
    return "HloModule m\n\n" + "\n".join(lines) + "\n"


class TestATX705TempBlowup:
    def test_seeded_copy_pileup_fires(self):
        # ten live 4 MiB copies vs an 8 MiB max working set: 5x > 4x default
        ctx = ctx_with_hlo(_temp_blowup_hlo(10))
        findings = list(rules_memory.atx705_temp_blowup(ctx))
        assert len(findings) == 1
        f = findings[0]
        assert f.severity == Severity.WARNING
        assert f.data["temp_bytes_at_peak"] == 10 * 1024 * 1024 * 4
        assert f.data["max_working_set_bytes"] == 2 * 1024 * 1024 * 4
        assert f.data["top_temps"][0]["op"] == "copy"

    def test_few_copies_quiet(self):
        ctx = ctx_with_hlo(_temp_blowup_hlo(2))
        assert list(rules_memory.atx705_temp_blowup(ctx)) == []


# --------------------------------------------------------- capacity planner
def _plan(**kw):
    base = dict(
        hbm_bytes=16 << 30,
        weights_bytes=4 << 30,
        kv_bytes_per_slot=8 << 20,
        n_slots=64,
        max_len=2048,
        act_peak_bytes=1 << 30,
        overhead_bytes=512 << 20,
    )
    base.update(kw)
    return capacity.plan_capacity(**base)


class TestCapacityPlanner:
    def test_arithmetic(self):
        p = _plan()
        assert p.kv_pool_bytes == 64 * (8 << 20)
        assert p.static_total_bytes == (
            (4 << 30) + 64 * (8 << 20) + (1 << 30) + (512 << 20)
        )
        assert p.free_bytes == (16 << 30) - (4 << 30) - (1 << 30) - (512 << 20)
        assert p.max_slots == p.free_bytes // (8 << 20)
        assert p.kv_bytes_per_token == (8 << 20) // 2048
        assert p.fits

    def test_max_blocks_paged_form(self):
        p = _plan()
        block_bytes = p.kv_bytes_per_token * 16
        assert p.max_blocks(16) == p.free_bytes // block_bytes
        # tokens, not slots: 16-token pages pack more contexts than slots do
        assert p.max_blocks(16) * 16 > p.max_slots

    def test_overfull_config_does_not_fit(self):
        p = _plan(n_slots=100_000)
        assert not p.fits
        assert "DOES NOT FIT" in p.format()
        assert p.max_slots < 100_000

    def test_capacity_error_carries_the_suggestion(self):
        p = _plan(n_slots=100_000)
        err = capacity.CapacityError(p)
        assert err.plan is p
        assert f"lower slots to <= {p.max_slots}" in str(err)
        assert "ATX_SERVE_CAPACITY_CHECK=0" in str(err)

    def test_tree_bytes(self):
        tree = {"a": np.zeros((4, 8), np.float32), "b": np.zeros(3, np.int8)}
        assert capacity.tree_bytes(tree) == 4 * 8 * 4 + 3


def _fake_engine(slots=4, max_len=64, kv_mib=1, weights_mib=2, pool_mib=1):
    """The attribute surface `plan_for_engine` reads, with numpy arrays."""
    return SimpleNamespace(
        params={"w": np.zeros((weights_mib << 20) // 4, np.float32)},
        _kv={"k": np.zeros((slots * kv_mib) << 20, np.int8)},
        _pool=np.zeros(pool_mib << 20, np.int8),
        n_slots=slots,
        max_len=max_len,
    )


class TestEngineCapacityGuard:
    def test_plan_for_engine_reads_the_pools(self):
        p = capacity.plan_for_engine(_fake_engine(), hbm_bytes=16 << 20)
        assert p.weights_bytes == 2 << 20
        assert p.kv_bytes_per_slot == 1 << 20
        assert p.overhead_bytes == 1 << 20
        assert p.n_slots == 4 and p.max_len == 64
        assert p.fits and p.max_slots == 13

    def test_atx706_severity_flips_on_fit(self):
        (ok,) = capacity.capacity_findings(_fake_engine(), hbm_bytes=16 << 20)
        assert ok.rule_id == "ATX706" and ok.severity == Severity.INFO
        assert ok.data["fits"] and ok.data["serve_static_max_slots"] == 13
        assert ok.data["max_blocks"]["16"] > 0
        (oom,) = capacity.capacity_findings(_fake_engine(), hbm_bytes=4 << 20)
        assert oom.severity == Severity.ERROR
        assert not oom.data["fits"]
        assert "OOM" in oom.message and oom.fix_hint

    def test_guard_modes(self):
        engine = _fake_engine()
        with patch_environment(
            atx_serve_capacity_check="0", atx_serve_capacity_hbm_mib="1"
        ):
            assert capacity.check_engine_capacity(engine) is None
        with patch_environment(
            atx_serve_capacity_check="warn", atx_serve_capacity_hbm_mib="1"
        ):
            with pytest.warns(RuntimeWarning, match="statically exceeds"):
                plan = capacity.check_engine_capacity(engine)
            assert plan is not None and not plan.fits
        with patch_environment(
            atx_serve_capacity_check="error", atx_serve_capacity_hbm_mib="1"
        ):
            with pytest.raises(capacity.CapacityError) as exc:
                capacity.check_engine_capacity(engine)
            assert exc.value.plan.max_slots == 0
        with patch_environment(
            atx_serve_capacity_check="error", atx_serve_capacity_hbm_mib="1024"
        ):
            plan = capacity.check_engine_capacity(engine)  # fits: no raise
            assert plan is not None and plan.fits

    def test_real_engine_init_raises_when_seeded_over_capacity(self):
        from accelerate_tpu import serving
        from accelerate_tpu.generation import GenerationConfig
        from accelerate_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(
            vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2
        )
        params = llama.init(jax.random.PRNGKey(1), cfg)

        def _apply(p, t, c):
            return llama.forward_with_cache(p, t, c, cfg)

        def _init_cache(b, m):
            return llama.init_cache(cfg, b, m)

        with patch_environment(
            atx_serve_capacity_check="error", atx_serve_capacity_hbm_mib="1"
        ):
            with pytest.raises(capacity.CapacityError):
                serving.Engine(
                    _apply, _init_cache, params, GenerationConfig(),
                    slots=3, buckets=(8, 16), max_len=96,
                )


# ------------------------------------------------------------ budget gate
def _memory_report(peak_mib=100.0, max_slots=64):
    return Report(
        findings=[
            Finding(
                "ATX701", Severity.INFO, "v5e", "peak", "",
                data={"peak_hbm_mib": peak_mib},
            ),
            Finding(
                "ATX706", Severity.INFO, "v5e", "capacity", "",
                data={"serve_static_max_slots": max_slots},
            ),
        ]
    )


class TestMemoryBudgetRatchet:
    def test_extracts_both_memory_series(self):
        series = perf_budget.extract_series(_memory_report())
        assert series["peak_hbm_mib"] == 100.0
        assert series["serve_static_max_slots"] == 64

    def test_peak_regression_fails(self):
        budgets = {"scn": perf_budget.extract_series(_memory_report())}
        worse = perf_budget.extract_series(_memory_report(peak_mib=110.0))
        problems = perf_budget.check_budgets(budgets, {"scn": worse})
        assert any("peak_hbm_mib" in p for p in problems)

    def test_slots_regression_fails(self):
        budgets = {"scn": perf_budget.extract_series(_memory_report())}
        worse = perf_budget.extract_series(_memory_report(max_slots=50))
        problems = perf_budget.check_budgets(budgets, {"scn": worse})
        assert any("serve_static_max_slots" in p for p in problems)

    def test_within_tolerance_holds(self):
        budgets = {"scn": perf_budget.extract_series(_memory_report())}
        wobble = perf_budget.extract_series(
            _memory_report(peak_mib=100.9, max_slots=63)
        )
        assert perf_budget.check_budgets(budgets, {"scn": wobble}) == []


# ------------------------------------------- ATX105 <-> ATX701 reconciliation
@pytest.fixture(scope="module")
def nlp_memory_report():
    """One shared lint of the real nlp_example step (the compile is the
    expensive part; the reconciliation assertions all read it)."""
    from accelerate_tpu.commands.lint import SCENARIOS

    AcceleratorState._reset_state()
    try:
        _, report = SCENARIOS["nlp_example"]()
    finally:
        AcceleratorState._reset_state()
    return report


class TestHbmReconciliation:
    def test_atx105_cites_the_compiled_timeline(self, nlp_memory_report):
        f = finding(nlp_memory_report, "ATX105")
        assert "ATX701 timeline" in f.message
        assert f.data["compiled_peak_hbm_bytes"] > 0
        assert f.data["first_order_total_bytes"] > 0

    def test_timeline_agrees_with_memory_analysis(self, nlp_memory_report):
        f = finding(nlp_memory_report, "ATX701")
        assert f.data["cross_check"], "no memory_analysis totals on this backend"
        for key, err in f.data["cross_check"].items():
            assert err < 0.05, (key, err)

    def test_no_memory_errors_on_the_clean_example(self, nlp_memory_report):
        errors = [
            f for f in nlp_memory_report.findings
            if f.rule_id.startswith("ATX70") and f.severity >= Severity.ERROR
        ]
        assert not errors, [f.format() for f in errors]
