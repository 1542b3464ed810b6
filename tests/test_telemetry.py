"""Unified runtime telemetry tests (docs/observability.md).

Five layers of proof:

- **registry math**: counter/gauge/histogram semantics — label series
  isolation, inclusive ``le`` bucket assignment, rank-interpolated
  quantiles (incl. the +Inf overflow clamp), and the get-or-create
  conflict guard (`MetricError` on kind/label/bucket forks);
- **cross-process export**: per-process JSON snapshots written atomically,
  merged proc-0 style — counters and histogram buckets sum, gauges reduce
  per their declared aggregate — with NO collectives anywhere (the lint
  `telemetry` host-loop scenario pins that side);
- **Prometheus round-trip**: the text exposition parses with an
  independent mini-parser, buckets are cumulative and end at ``+Inf`` ==
  count, and a quantile recomputed from the exported text matches the
  registry's own estimate;
- **endpoint lifecycle**: `/metrics`, `/metrics.json`, `/healthz` on an
  ephemeral port; `?fleet=1` serves the snapshot-dir merge; `close()`
  releases the port for rebinding;
- **hot-path safety**: `StepStats` makes ZERO device syncs with the
  sampler off (counted via the `_block_until_ready` indirection), the
  compile counter follows jit cache-size deltas, and training losses are
  bit-identical under ``ATX_METRICS=0`` vs ``1``.
"""

import json
import os
import re
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import telemetry
from accelerate_tpu.telemetry import (
    MetricError,
    MetricsServer,
    Registry,
    StatsView,
    StepStats,
)
from accelerate_tpu.telemetry import registry as registry_mod
from accelerate_tpu.telemetry import spans as spans_mod
from accelerate_tpu.telemetry import stepstats as stepstats_mod
from accelerate_tpu.utils.environment import patch_environment


# --------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_labels_isolate_series(self):
        reg = Registry()
        c = reg.counter("reqs", "requests", labels=("engine",))
        c.inc(engine="0")
        c.inc(2, engine="1")
        assert c.value(engine="0") == 1.0
        assert c.value(engine="1") == 3.0 - 1.0
        assert c.value(engine="missing") == 0.0

    def test_gauge_set_and_inc(self):
        reg = Registry()
        g = reg.gauge("depth", "queue depth")
        g.set(4)
        g.inc(-1)
        assert g.value() == 3.0

    def test_get_or_create_returns_same_object(self):
        reg = Registry()
        assert reg.counter("a") is reg.counter("a")

    def test_kind_conflict_raises(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(MetricError, match="already registered"):
            reg.gauge("x")

    def test_label_conflict_raises(self):
        reg = Registry()
        reg.counter("x", labels=("engine",))
        with pytest.raises(MetricError, match="label mismatch"):
            reg.counter("x", labels=("cache",))

    def test_bucket_conflict_raises(self):
        reg = Registry()
        reg.histogram("h", buckets=(1, 2))
        with pytest.raises(MetricError, match="bucket mismatch"):
            reg.histogram("h", buckets=(1, 2, 3))

    def test_bad_gauge_aggregate_raises(self):
        reg = Registry()
        with pytest.raises(MetricError, match="aggregate"):
            reg.gauge("g", aggregate="median")

    def test_unknown_label_name_rejected(self):
        reg = Registry()
        c = reg.counter("c", labels=("engine",))
        with pytest.raises(MetricError):
            c.inc(router="0")


# -------------------------------------------------------------- histogram
class TestHistogram:
    def test_le_is_inclusive(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0, 2.0))
        h.observe(1.0)  # exactly on a bound -> that bucket, Prometheus-style
        snap = reg.snapshot()
        (entry,) = [m for m in snap["metrics"] if m["name"] == "h"]
        assert entry["series"][0]["bucket_counts"] == [1, 0, 0]

    def test_count_sum_mean(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(10.0, 100.0))
        for v in (1.0, 5.0, 30.0):
            h.observe(v)
        assert h.count() == 3
        assert h.sum() == 36.0
        assert h.mean() == 12.0

    def test_quantile_linear_interpolation(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
        for _ in range(2):
            h.observe(0.5)  # bucket (0, 1]
        for _ in range(2):
            h.observe(5.0)  # bucket (1, 10]
        # rank(0.5) = 2 -> exactly consumes the first bucket: q50 = 1.0
        assert h.quantile(0.50) == pytest.approx(1.0)
        # rank(0.75) = 3 -> halfway through (1, 10]: 1 + 9 * 0.5
        assert h.quantile(0.75) == pytest.approx(5.5)

    def test_overflow_clamps_to_top_bound(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0, 2.0))
        h.observe(1e9)
        assert h.quantile(0.99) == 2.0

    def test_empty_series_quantile_is_none(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0,))
        assert h.quantile(0.5) is None
        assert h.mean() is None


# ----------------------------------------------------- snapshots / merge
class TestSnapshots:
    def _registry(self, steps: float, depth: float) -> Registry:
        reg = Registry()
        reg.counter("steps").inc(steps)
        reg.gauge("depth_max", aggregate="max").set(depth)
        reg.gauge("tps_sum", aggregate="sum").set(depth)
        reg.gauge("lag_mean", aggregate="mean").set(depth)
        h = reg.histogram("lat", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(steps)
        return reg

    def test_write_read_merge(self, tmp_path):
        d = str(tmp_path)
        telemetry.write_snapshot(d, registry=self._registry(3, 2.0), process_index=0)
        telemetry.write_snapshot(d, registry=self._registry(5, 6.0), process_index=1)
        assert sorted(os.listdir(d)) == ["metrics_0.json", "metrics_1.json"]
        merged = telemetry.aggregate_snapshots(d)
        assert merged["processes"] == 2
        by_name = {m["name"]: m for m in merged["metrics"]}
        assert by_name["steps"]["series"][0]["value"] == 8.0  # counters sum
        assert by_name["depth_max"]["series"][0]["value"] == 6.0
        assert by_name["tps_sum"]["series"][0]["value"] == 8.0
        assert by_name["lag_mean"]["series"][0]["value"] == 4.0
        lat = by_name["lat"]["series"][0]
        assert lat["count"] == 4  # histogram buckets sum
        assert lat["bucket_counts"][0] == 2

    def test_snapshot_file_is_valid_json(self, tmp_path):
        d = str(tmp_path)
        telemetry.write_snapshot(d, registry=self._registry(1, 1.0))
        with open(os.path.join(d, "metrics_0.json")) as f:
            snap = json.load(f)
        assert snap["version"] == 1
        assert any(m["name"] == "steps" for m in snap["metrics"])

    def test_merged_snapshot_renders_prometheus(self, tmp_path):
        d = str(tmp_path)
        telemetry.write_snapshot(d, registry=self._registry(1, 1.0), process_index=0)
        telemetry.write_snapshot(d, registry=self._registry(1, 1.0), process_index=1)
        text = telemetry.render_snapshot_prometheus(telemetry.aggregate_snapshots(d))
        assert "# TYPE steps counter" in text
        assert re.search(r"^steps 2(\.0)?$", text, re.M)


# --------------------------------------------------------- prometheus text
def _parse_prometheus(text: str) -> dict:
    """Independent mini-parser: name -> [(labels, value)], '#types' -> kinds."""
    out: dict = {"#types": {}}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            out["#types"][name] = kind
        elif line and not line.startswith("#"):
            m = re.match(r"^(\w+)(?:\{(.*)\})?\s+(\S+)$", line)
            assert m, f"unparseable line: {line!r}"
            name, raw, value = m.groups()
            labels = dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', raw or ""))
            out.setdefault(name, []).append((labels, float(value)))
    return out


class TestPrometheusRoundTrip:
    def test_exposition_parses_and_buckets_are_cumulative(self):
        reg = Registry()
        h = reg.histogram("lat_ms", "latency", labels=("engine",), buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 3.0, 30.0, 3000.0):
            h.observe(v, engine="0")
        reg.counter("reqs", "requests").inc(4)
        parsed = _parse_prometheus(reg.render_prometheus())
        assert parsed["#types"] == {"lat_ms": "histogram", "reqs": "counter"}
        buckets = {lb["le"]: v for lb, v in parsed["lat_ms_bucket"]}
        assert buckets == {"1": 1.0, "10": 2.0, "100": 3.0, "+Inf": 4.0}
        assert parsed["lat_ms_count"][0][1] == 4.0
        assert parsed["lat_ms_sum"][0][1] == pytest.approx(3033.5)
        assert parsed["reqs"][0][1] == 4.0

    def test_quantile_recomputed_from_text_matches_registry(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
        rng = np.random.RandomState(0)
        for v in rng.uniform(0.1, 80.0, 200):
            h.observe(float(v))
        parsed = _parse_prometheus(reg.render_prometheus())
        entries = sorted(
            (float("inf") if lb["le"] == "+Inf" else float(lb["le"]), v)
            for lb, v in parsed["lat_bucket"]
        )
        total = entries[-1][1]
        rank = 0.9 * total
        lo, cum = 0.0, 0.0
        for bound, c in entries:
            if c >= rank:
                est = lo + (bound - lo) * (rank - cum) / max(c - cum, 1)
                break
            lo, cum = bound, c
        assert est == pytest.approx(h.quantile(0.9), rel=1e-6)

    def test_label_values_escaped(self):
        reg = Registry()
        reg.counter("c", labels=("path",)).inc(path='a"b\\c\nd')
        text = reg.render_prometheus()
        assert 'path="a\\"b\\\\c\\nd"' in text


# ---------------------------------------------------------------- endpoint
class TestMetricsServer:
    def _get(self, url: str) -> str:
        return urllib.request.urlopen(url, timeout=5).read().decode()

    def test_routes_and_lifecycle(self):
        reg = Registry()
        reg.counter("up").inc()
        with MetricsServer(port=0, registry=reg) as srv:
            port = srv.port
            base = f"http://127.0.0.1:{port}"
            assert re.search(r"^up 1(\.0)?$", self._get(base + "/metrics"), re.M)
            body = json.loads(self._get(base + "/metrics.json"))
            assert any(m["name"] == "up" for m in body["metrics"])
            assert self._get(base + "/healthz").strip() == "ok"
            with pytest.raises(urllib.error.HTTPError):
                self._get(base + "/nope")
        # Closed: the port is released and can be rebound immediately.
        with pytest.raises(urllib.error.URLError):
            self._get(f"http://127.0.0.1:{port}/healthz")
        srv2 = MetricsServer(port=port, registry=reg)
        try:
            assert self._get(f"http://127.0.0.1:{port}/healthz").strip() == "ok"
        finally:
            srv2.close()

    def test_fleet_merge_route(self, tmp_path):
        d = str(tmp_path)
        for proc, steps in ((0, 3), (1, 4)):
            reg = Registry()
            reg.counter("steps").inc(steps)
            telemetry.write_snapshot(d, registry=reg, process_index=proc)
        with MetricsServer(port=0, registry=Registry(), snapshot_dir=d) as srv:
            text = self._get(f"http://127.0.0.1:{srv.port}/metrics?fleet=1")
        assert re.search(r"^steps 7(\.0)?$", text, re.M)


# --------------------------------------------------------------- StatsView
class TestStatsView:
    def test_dict_protocol_over_registry(self):
        reg = Registry()
        view = StatsView("eng", ("hits", "misses"), label="engine", registry=reg)
        assert dict(view) == {"hits": 0, "misses": 0}
        view["hits"] += 2
        assert view["hits"] == 2 and isinstance(view["hits"], int)
        assert reg.counter("eng_hits", labels=("engine",)).value(
            engine=view.instance
        ) == 2.0
        with pytest.raises(KeyError):
            view["nope"]
        with pytest.raises(TypeError):
            del view["hits"]

    def test_instances_do_not_share_series(self):
        reg = Registry()
        a = StatsView("eng", ("hits",), label="engine", registry=reg)
        b = StatsView("eng", ("hits",), label="engine", registry=reg)
        a["hits"] += 5
        assert b["hits"] == 0


# --------------------------------------------------------------- StepStats
class TestStepStats:
    def test_zero_device_syncs_with_sampler_off(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            stepstats_mod, "_block_until_ready", lambda x: calls.append(x)
        )
        stats = StepStats(registry=Registry(), sample_every=0)
        for _ in range(5):
            stats.on_entry(tokens_per_step=64)
            stats.on_dispatched(outputs={"loss": 1.0}, cache_size=1)
        assert calls == []
        assert stats.steps == 5

    def test_sampler_blocks_on_schedule(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            stepstats_mod, "_block_until_ready", lambda x: calls.append(x)
        )
        stats = StepStats(registry=Registry(), sample_every=2)
        for _ in range(5):
            stats.on_entry()
            stats.on_dispatched(outputs="out", cache_size=1)
        assert len(calls) == 2  # steps 2 and 4
        assert "train_device_ms" in stats.latest()

    def test_compile_counter_follows_cache_deltas(self):
        stats = StepStats(registry=Registry(), sample_every=0)
        for cache_size in (1, 1, 2, 2, 3):
            stats.on_entry()
            stats.on_dispatched(cache_size=cache_size)
        assert stats.compiles == 3
        assert stats.latest()["train_compiles"] == 3.0

    def test_hfu_never_resolves_flops_when_peak_unknown(self):
        resolved = []
        stats = StepStats(
            registry=Registry(),
            sample_every=0,
            flops_fn=lambda: resolved.append(1) or 1e12,
            peak_flops_total=None,  # CPU: chip peak unknown
        )
        for _ in range(3):
            stats.on_entry(tokens_per_step=8)
            stats.on_dispatched()
        assert resolved == []
        assert stats.latest()["train_hfu"] == 0.0

    def test_hfu_with_known_peak(self):
        import time

        stats = StepStats(
            registry=Registry(),
            sample_every=0,
            ema_alpha=1.0,
            flops_fn=lambda: 1e6,
            peak_flops_total=1e12,
        )
        for _ in range(3):
            stats.on_entry(tokens_per_step=8)
            stats.on_dispatched()
            time.sleep(0.005)
        latest = stats.latest()
        assert latest["train_step_ms"] > 0
        # ema_alpha=1: hfu == flops / (last_interval * peak), ~2e-4 for a
        # ~5 ms loop — the point is it resolved flops_fn and is sane.
        assert 0 < latest["train_hfu"] < 1.0

    def test_tokens_in_batch_prefers_integer_leaves(self):
        batch = {
            "input_ids": np.zeros((4, 128), np.int32),
            "embeds": np.zeros((4, 512), np.float32),
        }
        assert stepstats_mod.tokens_in_batch(batch) == 4 * 128
        assert stepstats_mod.tokens_in_batch({"x": np.zeros((2, 3), np.float32)}) == 6


# ------------------------------------------------------------------- spans
class TestSpans:
    def test_span_jsonl_and_chrome_trace(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        spans_mod.start_trace_log(path)
        try:
            with spans_mod.span("outer", phase="train"):
                with spans_mod.span("inner"):
                    pass
        finally:
            spans_mod.stop_trace_log()
        events = [json.loads(l) for l in open(path)]
        assert [e["name"] for e in events] == ["inner", "outer"]  # close order
        inner, outer = events
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["phase"] == "train"
        trace = spans_mod.chrome_trace(path)
        assert {e["ph"] for e in trace["traceEvents"]} == {"X"}

    def test_span_without_writer_writes_nowhere(self, tmp_path, monkeypatch):
        # No log open: the span is the bare profiler annotation (one flag
        # check while nobody captures) and must not write anywhere or raise.
        monkeypatch.chdir(tmp_path)
        assert spans_mod.trace_log_path() is None
        with spans_mod.span("nothing", bucket=8):
            pass
        with spans_mod.step_span(3):
            pass
        assert os.listdir(tmp_path) == []

    @pytest.mark.parametrize("log_open", [False, True], ids=["no-log", "log-open"])
    def test_span_reaches_a_capture_nobody_told_the_program_about(
        self, tmp_path, host_capture, log_open
    ):
        """A capture started by bare `jax.profiler.start_trace` (a benchmark,
        a profiler server's button) holds the program's spans on its host
        plane, attributes included: there is no gate to raise first."""
        if log_open:
            spans_mod.start_trace_log(str(tmp_path / "spans.jsonl"))
        try:
            events = {e["name"]: e for e in host_capture(_spans_under_test)}
        finally:
            spans_mod.stop_trace_log()
        assert events["unit_outer"]["stats"] == {"bucket": 64, "rid": 7}
        assert events["train_step"]["stats"]["step"] == 5
        assert events["train"]["stats"]["step_num"] == 5  # the StepTraceAnnotation
        o, i = events["unit_outer"], events["unit_inner"]
        assert o["start"] <= i["start"] and i["end"] <= o["end"]
        if log_open:
            logged = [json.loads(l) for l in open(tmp_path / "spans.jsonl")]
            assert [e["name"] for e in logged] == ["unit_inner", "unit_outer", "train_step"]

    def test_no_capture_gate_is_left_in_the_package(self):
        import accelerate_tpu
        from accelerate_tpu.utils import profiler

        for gone in ("trace_active", "maybe_step_annotation", "_ACTIVE_TRACES"):
            assert not hasattr(profiler, gone)
        assert not hasattr(spans_mod, "current_span")
        assert not hasattr(telemetry, "spans_enabled")
        root = os.path.dirname(accelerate_tpu.__file__)
        for base, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    text = open(os.path.join(base, f)).read()
                    assert "trace_active" not in text and "maybe_step_annotation" not in text, f


def _spans_under_test():
    with spans_mod.span("unit_outer", bucket=64, rid=7):
        with spans_mod.span("unit_inner"):
            pass
    with spans_mod.step_span(5):
        pass


# ------------------------------------------------- training integration
def _train_losses(n_steps: int = 4) -> tuple[list, object]:
    from accelerate_tpu.accelerator import Accelerator, TrainState
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    acc = Accelerator(seed=0)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)}
    state = acc.prepare_train_state(
        TrainState.create(params=params, tx=optax.sgd(1e-2))
    )
    step = acc.make_train_step(lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2))
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(n_steps):
        batch = {"x": rng.randn(8, 8).astype(np.float32)}
        state, metrics = step(state, batch)
        losses.append(np.asarray(metrics["loss"]).item())
    return losses, step


class TestTrainingIntegration:
    def test_losses_bit_identical_metrics_on_off(self):
        with patch_environment(ATX_METRICS="0"):
            off, step_off = _train_losses()
        with patch_environment(ATX_METRICS="1"):
            on, step_on = _train_losses()
        with patch_environment(ATX_METRICS="1", ATX_METRICS_SAMPLE_EVERY="2"):
            sampled, _ = _train_losses()
        assert off == on == sampled  # bit-identical, not approx
        assert step_off.step_stats is None
        assert step_on.step_stats is not None

    def test_step_stats_armed_and_counting(self):
        with patch_environment(ATX_METRICS="1"):
            _, step = _train_losses(3)
        stats = step.step_stats
        assert stats.steps == 3
        assert stats.compiles == 1  # one shape -> one jit entry
        latest = stats.latest()
        assert latest["train_step_ms"] > 0
        assert latest["train_hfu"] == 0.0  # CPU: peak unknown
        assert "train_device_ms" not in latest  # sampler off -> no syncs

    def test_train_steps_are_numbered_in_a_bare_capture(self, host_capture):
        """`Accelerator`'s step helper enters `step_span` around the jitted
        call: a capture it knows nothing of shows numbered steps."""
        events = host_capture(lambda: _train_losses(3))
        steps = [e["stats"]["step_num"] for e in events if e["name"] == "train"]
        assert steps == [1, 2, 3]
        assert [e["stats"]["step"] for e in events if e["name"] == "train_step"] == [1, 2, 3]

    def test_zero_syncs_through_real_train_loop(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            stepstats_mod, "_block_until_ready", lambda x: calls.append(x)
        )
        with patch_environment(ATX_METRICS="1"):
            _train_losses(4)
        assert calls == []  # default ATX_METRICS_SAMPLE_EVERY=0: never block

    def test_end_training_writes_snapshot(self, tmp_path):
        from accelerate_tpu.accelerator import Accelerator
        from accelerate_tpu.state import AcceleratorState

        d = str(tmp_path / "snap")
        with patch_environment(ATX_METRICS="1", ATX_METRICS_DIR=d):
            AcceleratorState._reset_state()
            acc = Accelerator(seed=0)
            acc.end_training()
        assert os.path.isfile(os.path.join(d, "metrics_0.json"))
