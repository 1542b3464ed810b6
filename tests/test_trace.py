"""Request-scoped tracing, flight recorder, and postmortem bundles
(`accelerate_tpu/telemetry/flight.py`, `accelerate_tpu/commands/trace.py`).

The ISSUE-15 acceptance matrix:

- **flight recorder mechanics**: the bounded ring keeps the newest
  `capacity` records oldest-first through wraparound, `total` keeps
  counting past the wrap, and `record_span` defaults make instant
  markers;
- **postmortem bundles**: `dump_postmortem` -> `read_bundle` round-trips
  the schema (spans, metrics snapshot, thread stacks, fault points), and
  a bundle is refused when the spans key is missing;
- **bit-identity**: greedy outputs through a 2-replica Router are
  BIT-IDENTICAL with ``ATX_TRACE_REQUESTS=1`` vs ``0`` — tracing must
  never perturb the numerics; with it off the ring holds exactly one
  `request` record per completion, its five timestamps in order;
- **engine step phases**: in a capture the engine knows nothing of, the
  `serve_admit` / `serve_dispatch` / `serve_fetch` / `serve_emit` spans
  tile each `serve_prefill` / `serve_decode` parent; the fetches' `chunks`
  and `decodes` name every program dispatched once, and a fetch made to
  wait is counted and its `fetch_stall` record carries its span's `seq`;
- **exactly-once semantics through failover**: a replica killed
  mid-decode leaves BOTH dispatch spans in the trace (attempt 1 and the
  retry), while stream spans still count each delivered token once;
- **phase attribution**: queue+prefill+decode+emit spans tile
  [submitted, finished] so `atx trace --check` passes at 5%;
- **SystemExit flush**: the spans JSONL writer flushes via atexit so a
  process dying at a fault point (exit 75) leaves a parseable trace.

`make smoke-trace` runs this file plus `tests/scripts/trace_smoke.py`
and the `atx lint tracing --multihost 2` replay.
"""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from accelerate_tpu import serving, telemetry
from accelerate_tpu.commands import trace as trace_cmd
from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.models import llama
from accelerate_tpu.serving import Router
from accelerate_tpu.telemetry import flight
from accelerate_tpu.test_utils import faults
from accelerate_tpu.utils.environment import patch_environment

CFG = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.PRNGKey(1), CFG)


def _apply(p, t, c):
    return llama.forward_with_cache(p, t, c, CFG)


def _init_cache(b, m):
    return llama.init_cache(CFG, b, m)


def _engine(params, config=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("buckets", (8,))
    kw.setdefault("max_len", 96)
    kw.setdefault("prefix_cache", False)
    return serving.Engine(_apply, _init_cache, params, config or GenerationConfig(), **kw)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    faults._reset_counters()
    flight.reset_recorder()
    yield
    faults._reset_counters()
    flight.reset_recorder()


def _requests(n, *, seed=0, budgets=(3, 6)):
    rng = np.random.RandomState(seed)
    return [
        serving.Request(
            prompt=rng.randint(0, 61, (int(rng.randint(3, 20)),)).astype(np.int32),
            max_new_tokens=int(rng.choice(budgets)),
            rid=i,
            seed=i,
        )
        for i in range(n)
    ]


def _spans_by_name(name):
    return [e for e in flight.recorder().last() if e["name"] == name]


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_wraparound_keeps_newest_oldest_first(self):
        rec = flight.FlightRecorder(capacity=4)
        for i in range(10):
            rec.record({"name": f"s{i}", "rid": i, "t0": float(i), "t1": float(i)})
        assert rec.total == 10
        kept = rec.last()
        assert [e["name"] for e in kept] == ["s6", "s7", "s8", "s9"]
        assert [e["name"] for e in rec.last(2)] == ["s8", "s9"]
        rec.clear()
        assert rec.total == 0 and rec.last() == []

    def test_capacity_env_knob(self):
        with patch_environment(ATX_FLIGHT_RECORDER_SPANS="2"):
            rec = flight.FlightRecorder()
        assert rec.capacity == 2
        with patch_environment(ATX_FLIGHT_RECORDER_SPANS="bogus"):
            assert flight.FlightRecorder().capacity == flight.DEFAULT_CAPACITY

    def test_record_span_defaults_to_instant_marker(self):
        flight.record_span("mark", rid=7, note="x")
        (entry,) = flight.recorder().last()
        assert entry["rid"] == 7
        assert entry["t0"] == entry["t1"]
        assert entry["attrs"] == {"note": "x"}

    def test_trace_requests_enabled_values(self):
        for raw, want in (("1", True), ("true", True), ("YES", True),
                          ("0", False), ("", False), ("off", False)):
            with patch_environment(ATX_TRACE_REQUESTS=raw):
                assert flight.trace_requests_enabled() is want


# ------------------------------------------------------ postmortem bundles
class TestPostmortem:
    def test_bundle_round_trip(self, tmp_path):
        flight.record_span("phase_queue", rid=3, t0=1.0, t1=2.0)
        with patch_environment(ATX_FAULT_RAISE_AT="demo.point@1"):
            path = flight.dump_postmortem(
                "unit test: weird/reason", str(tmp_path), extra={"k": 1}
            )
        assert path is not None and os.path.isfile(path)
        assert os.path.basename(path).startswith("postmortem_unit_test")
        bundle = flight.read_bundle(path)
        assert bundle["version"] == flight.BUNDLE_VERSION
        assert bundle["reason"] == "unit test: weird/reason"
        assert bundle["pid"] == os.getpid()
        assert bundle["spans_total"] == 1
        (span,) = bundle["spans"]
        assert span["name"] == "phase_queue" and span["rid"] == 3
        assert "thread_stacks" in bundle and "MainThread" in bundle["thread_stacks"]
        assert "metrics" in bundle or "metrics_error" in bundle
        assert bundle["fault_points"]["env"]["ATX_FAULT_RAISE_AT"] == "demo.point@1"
        assert bundle["extra"] == {"k": 1}

    def test_no_directory_means_no_bundle(self):
        with patch_environment(ATX_POSTMORTEM_DIR=""):
            assert flight.dump_postmortem("nowhere") is None

    def test_env_dir_used_when_no_explicit_dir(self, tmp_path):
        d = str(tmp_path / "pm")
        with patch_environment(ATX_POSTMORTEM_DIR=d):
            path = flight.dump_postmortem("envdir")
        assert path is not None and path.startswith(d)

    def test_read_bundle_rejects_non_bundles(self, tmp_path):
        p = str(tmp_path / "not_a_bundle.json")
        with open(p, "w") as f:
            json.dump({"hello": 1}, f)
        with pytest.raises(ValueError, match="no 'spans'"):
            flight.read_bundle(p)


# --------------------------------------------------------- traced serving
class TestTracedServing:
    def _serve(self, params, reqs):
        with Router([_engine(params), _engine(params)]) as router:
            completions = router.serve(reqs)
        return {c.rid: c for c in completions}

    def test_bit_identity_tracing_on_vs_off(self, params):
        reqs = _requests(8)
        with patch_environment(ATX_TRACE_REQUESTS="0"):
            off = self._serve(params, reqs)
        # Off is the always-on black box and nothing finer: one `request`
        # record per completion (and a `fetch_stall` record for a fetch a
        # loaded machine made wait, which is rare and not a finer span).
        records = [r for r in flight.recorder().last() if r["name"] != "fetch_stall"]
        assert [r["name"] for r in records] == ["request"] * 8
        assert {r["rid"] for r in records} == set(off)
        for r in records:
            c, a = off[r["rid"]], r["attrs"]
            stamps = [r["t0"], a["admitted_at"], a["prefill_started_at"],
                      a["first_token_at"], r["t1"]]
            assert stamps == sorted(stamps) and stamps[0] > 0
            assert stamps[1:] == [c.admitted_at, c.prefill_started_at,
                                  c.first_token_at, c.finished_at]
            assert c.submitted_at <= r["t0"]  # the router's admission came first
            assert a["prompt_tokens"] == len(c.prompt) and a["new_tokens"] == c.n_new
            assert a["finish_reason"] == c.finish_reason and a["engine"]
        with patch_environment(ATX_TRACE_REQUESTS="1"):
            on = self._serve(params, _requests(8))
        assert len(_spans_by_name("request")) == 16  # still one a completion
        assert flight.recorder().total > 16
        assert set(on) == set(off)
        for rid in off:
            np.testing.assert_array_equal(
                off[rid].tokens, on[rid].tokens,
                err_msg=f"rid {rid}: tracing perturbed the output",
            )

    def test_cancelled_request_is_recorded_and_left_out_of_the_summary(self, params):
        engine = _engine(params)
        done = engine.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        completions = engine.run_until_idle()
        queued = engine.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        assert engine.cancel(queued).finish_reason == "cancelled"
        by_rid = {r["rid"]: r for r in _spans_by_name("request")}
        assert set(by_rid) == {done, queued}
        assert by_rid[queued]["attrs"]["finish_reason"] == "cancelled"
        assert by_rid[queued]["attrs"]["admitted_at"] == 0.0  # never reached a slot
        # exact samples, not bucket estimates: one finished request is its own median
        (c,) = completions
        summary = engine.latency_summary()
        assert summary["p50_ms"] == pytest.approx((c.finished_at - c.submitted_at) * 1e3)
        assert summary["ttft_p50_ms"] == pytest.approx((c.first_token_at - c.submitted_at) * 1e3)
        assert _engine(params).latency_summary()["p50_ms"] is None  # another engine's ring share

    @pytest.mark.parametrize("decode_block", [1, 3])
    def test_step_phase_spans_tile_their_parent(self, params, host_capture, decode_block):
        """Started by the bare profiler API, with no `profile()` context and
        no span log: every engine step is one `serve_prefill` or
        `serve_decode` span whose children, in order and without overlap,
        are admit, dispatch, fetch (when the host waits for a token) and
        emit, and cover it."""
        engine = _engine(params, decode_block=decode_block)
        for r in _requests(5, seed=2):
            engine.submit_request(r)
        engine.step()  # compile outside the capture
        events = host_capture(engine.run_until_idle)
        parents = [e for e in events if e["name"] in ("serve_prefill", "serve_decode")]
        assert len(parents) == len(engine.actions) - 1
        assert [p["name"].removeprefix("serve_") for p in parents] == engine.actions[1:]
        covered = total = 0
        for parent in parents:
            children = [
                e for e in events
                if e["name"] in ("serve_admit", "serve_dispatch", "serve_fetch", "serve_emit")
                and parent["start"] <= e["start"] and e["end"] <= parent["end"]
            ]
            names = [c["name"] for c in children]
            if parent["name"] == "serve_decode":
                assert names == ["serve_admit", "serve_dispatch", "serve_fetch", "serve_emit"]
                assert children[1]["stats"]["resident"] >= 1
                assert 1 <= children[1]["stats"]["block"] <= decode_block
            else:  # fetch and emit only on a prompt's last chunk
                assert names in (["serve_admit", "serve_dispatch"],
                                 ["serve_admit", "serve_dispatch", "serve_fetch", "serve_emit"])
                assert children[1]["stats"]["bucket"] == 8
                assert {"slot", "rid"} <= set(children[1]["stats"])
            for a, b in zip(children, children[1:]):
                assert a["end"] <= b["start"]
            covered += sum(c["end"] - c["start"] for c in children)
            total += parent["end"] - parent["start"]
        assert covered / total > 0.9  # the rest is the Python between two spans
        blocks = [e["stats"]["block"] for e in events
                  if e["name"] == "serve_dispatch" and "block" in e["stats"]]
        assert max(blocks) == decode_block

    @pytest.mark.parametrize("decode_block", [1, 3])
    def test_every_dispatched_program_is_waited_on_by_one_fetch(self, params, host_capture, decode_block):
        """Over a run to idle, the `serve_fetch` spans, numbered by `seq`,
        name as many prefill chunks and decode steps as the engine
        dispatched: each program is waited on by exactly one fetch."""
        engine = _engine(params, decode_block=decode_block)
        for r in _requests(5, seed=2):
            engine.submit_request(r)
        events = host_capture(engine.run_until_idle)
        fetches = [e["stats"] for e in events if e["name"] == "serve_fetch"]
        assert [f["seq"] for f in fetches] == list(range(1, len(fetches) + 1))
        assert sum(f["chunks"] for f in fetches) == engine.stats["prefill_chunks"] > len(_requests(5, seed=2))
        assert sum(f["decodes"] for f in fetches) == engine.stats["decode_steps"]
        if decode_block > 1:
            assert max(f["decodes"] for f in fetches) > 1

    def test_a_stalled_fetch_is_counted_and_joins_its_span(self, params, host_capture, monkeypatch):
        """A fetch that waits 0.2 s longer than the same work usually does is
        counted, and its `fetch_stall` record carries its span's `seq`. Other
        waits of a loaded machine may be flagged too: only the injected one
        is asserted."""
        engine = _engine(params)
        reqs = lambda seed: [
            serving.Request(prompt=np.arange(1, 9, dtype=np.int32) + seed, max_new_tokens=16, rid=-1, seed=seed)
            for seed in range(seed, seed + 4)
        ]
        for r in reqs(0):  # compiles; a prompt of one chunk, so a decode fetch waits on "decode" alone
            engine.submit_request(r)
        engine.run_until_idle()
        real, decode_fetches = jax.device_get, []

        def slow(pending):
            if isinstance(pending, list):  # the decode step's fetch
                decode_fetches.append(1)
                if len(decode_fetches) == 6:
                    time.sleep(0.2)
            return real(pending)

        monkeypatch.setattr(jax, "device_get", slow)
        for r in reqs(10):
            engine.submit_request(r)
        events = host_capture(engine.run_until_idle)
        (slow_span,) = [e for e in events if e["name"] == "serve_fetch" and e["end"] - e["start"] >= 0.2e9]
        stalls = {r["attrs"]["seq"]: r for r in _spans_by_name("fetch_stall")}
        record = stalls[slow_span["stats"]["seq"]]
        assert record["attrs"]["key"] == "decode" and slow_span["stats"]["decodes"] == 1
        assert record["attrs"]["wait_ms"] >= 200.0 > 1.5 * record["attrs"]["usual_ms"]
        assert record["t1"] - record["t0"] == pytest.approx(record["attrs"]["wait_ms"] / 1e3)
        assert record["attrs"]["cpu_ms"] >= 0.0 and record["attrs"]["engine"] == engine.stats.instance
        assert engine.stats["stalled_fetches"] == len(stalls) >= 1
        assert engine.stats["stalled_fetch_ms"] == pytest.approx(
            sum(r["attrs"]["wait_ms"] - r["attrs"]["usual_ms"] for r in stalls.values())
        )
        exported = {
            m["name"]: s["value"]
            for m in telemetry.snapshot()["metrics"] if m["name"] in ("serve_stalled_fetches", "serve_stalled_fetch_ms")
            for s in m["series"] if s["labels"] == engine.stats.labels
        }
        assert exported == {"serve_stalled_fetches": len(stalls), "serve_stalled_fetch_ms": engine.stats["stalled_fetch_ms"]}

    def test_request_lifecycle_spans_present(self, params):
        with patch_environment(ATX_TRACE_REQUESTS="1"):
            outs = self._serve(params, _requests(4))
        names = {e["name"] for e in flight.recorder().last()}
        for required in ("admission", "dispatch", "admit", "prefill_chunk",
                         "phase_queue", "phase_prefill", "phase_decode",
                         "phase_emit", "stream", "complete"):
            assert required in names, f"missing span kind {required!r}"
        admissions = _spans_by_name("admission")
        assert {e["attrs"]["decision"] for e in admissions} == {"accepted"}
        assert {e["rid"] for e in admissions} == set(outs)
        for e in _spans_by_name("prefill_chunk"):
            assert e["attrs"]["bucket"] >= 1
            assert isinstance(e["attrs"]["compile_miss"], bool)

    def test_phase_spans_sum_to_e2e_within_5pct(self, params, tmp_path):
        with patch_environment(ATX_TRACE_REQUESTS="1"):
            outs = self._serve(params, _requests(6, seed=3))
            bundle = flight.dump_postmortem("phase_check", str(tmp_path))
        records = trace_cmd.load_records(bundle)
        by_rid = trace_cmd.summarize(records)
        assert set(outs).issubset(by_rid)
        problems = trace_cmd.check_sums(by_rid, 0.05)
        assert problems == []
        rows = trace_cmd.attribution(by_rid)
        assert [r["phase"] for r in rows] == ["queue", "prefill", "decode", "emit"]
        assert sum(r["share"] for r in rows) == pytest.approx(1.0, abs=0.02)

    def test_decode_span_carries_residency(self, params):
        with patch_environment(ATX_TRACE_REQUESTS="1"):
            outs = self._serve(params, _requests(4, budgets=(6,)))
        decodes = {e["rid"]: e for e in _spans_by_name("phase_decode")}
        assert set(decodes) == set(outs)
        for rid, e in decodes.items():
            # max_new=6 with the first token produced by prefill.
            assert e["attrs"]["tokens"] == outs[rid].n_new
            assert e["attrs"]["iterations"] >= outs[rid].n_new - 1
            assert 0.0 < e["attrs"]["occupancy"] <= 1.0

    def test_failover_dispatch_and_stream_spans_exactly_once(self, params):
        reqs = _requests(6, seed=1, budgets=(6,))
        with patch_environment(
            ATX_TRACE_REQUESTS="1", ATX_FAULT_RAISE_AT="router.replica0.step@3"
        ):
            with Router([_engine(params), _engine(params)]) as router:
                completions = router.serve(reqs)
        assert router.stats["replicas_lost"] == 1
        assert router.stats["retries"] >= 1
        dispatches: dict[int, list[dict]] = {}
        for e in _spans_by_name("dispatch"):
            dispatches.setdefault(e["rid"], []).append(e["attrs"])
        retried = {rid for rid, ds in dispatches.items() if len(ds) > 1}
        assert retried, "no request shows a failover re-dispatch span"
        for rid in retried:
            attempts = [d["attempt"] for d in dispatches[rid]]
            assert attempts == sorted(attempts) and attempts[0] == 1
            assert [d["retry"] for d in dispatches[rid]] == [False] + [True] * (
                len(attempts) - 1
            )
        # Stream spans: exactly one per delivered token, replay leaves none.
        streams: dict[int, int] = {}
        for e in _spans_by_name("stream"):
            streams[e["rid"]] = streams.get(e["rid"], 0) + 1
        for c in completions:
            assert streams.get(c.rid, 0) == c.n_new, (
                f"rid {c.rid}: {streams.get(c.rid, 0)} stream spans for "
                f"{c.n_new} tokens"
            )
        # The quarantine left a span even with no postmortem dir armed.
        (q,) = _spans_by_name("quarantine")
        assert q["attrs"]["replica"] == 0

    def test_quarantine_dumps_postmortem(self, params, tmp_path):
        d = str(tmp_path / "pm")
        with patch_environment(
            ATX_TRACE_REQUESTS="1",
            ATX_POSTMORTEM_DIR=d,
            ATX_FAULT_RAISE_AT="router.replica0.step@3",
        ):
            with Router([_engine(params), _engine(params)]) as router:
                router.serve(_requests(6, seed=1, budgets=(6,)))
        assert router.stats["replicas_lost"] == 1
        bundles = [f for f in os.listdir(d) if f.startswith("postmortem_")]
        assert bundles, "quarantine produced no postmortem bundle"
        bundle = flight.read_bundle(os.path.join(d, sorted(bundles)[0]))
        assert bundle["reason"].startswith("quarantine_replica0")
        names = {s["name"] for s in bundle["spans"]}
        assert "dispatch" in names  # the failed dispatch is in the black box


# ------------------------------------------------------------- atx trace
class TestTraceCommand:
    def _bundle(self, tmp_path):
        base = 100.0
        for rid in (0, 1):
            off = rid * 0.010
            flight.record_span("phase_queue", rid=rid, t0=base + off, t1=base + off + 0.002)
            flight.record_span("phase_prefill", rid=rid, t0=base + off + 0.002, t1=base + off + 0.005)
            flight.record_span("phase_decode", rid=rid, t0=base + off + 0.005, t1=base + off + 0.009)
            flight.record_span("phase_emit", rid=rid, t0=base + off + 0.009, t1=base + off + 0.010)
            flight.record_span("complete", rid=rid, t0=base + off, t1=base + off + 0.010,
                               attempts=1, finish_reason="length")
        return flight.dump_postmortem("cli_test", str(tmp_path))

    def _run(self, argv):
        from accelerate_tpu.commands.cli import main

        return main(["trace"] + argv)

    def test_waterfall_and_check_pass(self, tmp_path, capsys):
        bundle = self._bundle(tmp_path)
        assert self._run([bundle, "--check", "0.05"]) == 0
        out = capsys.readouterr()
        assert "rid 0" in out.out and "rid 1" in out.out
        assert "tail-latency attribution" in out.out
        assert "consistent within 5%" in out.err

    def test_check_fails_on_uncovered_gap(self, tmp_path, capsys):
        flight.record_span("phase_queue", rid=0, t0=1.0, t1=1.001)
        flight.record_span("phase_prefill", rid=0, t0=1.001, t1=1.002)
        flight.record_span("phase_decode", rid=0, t0=1.002, t1=1.003)
        flight.record_span("phase_emit", rid=0, t0=1.003, t1=1.004)
        # e2e claims 10 ms but phases only cover 4 ms: a 60% hole.
        flight.record_span("complete", rid=0, t0=1.0, t1=1.010, attempts=1)
        bundle = flight.dump_postmortem("gap", str(tmp_path))
        assert self._run([bundle, "--check", "0.05"]) == 1
        assert "phases sum to" in capsys.readouterr().err

    def test_json_output_and_rid_filter(self, tmp_path, capsys):
        bundle = self._bundle(tmp_path)
        assert self._run([bundle, "--rid", "1", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert list(payload["requests"]) == ["1"]
        assert payload["requests"]["1"]["e2e_ms"] == pytest.approx(10.0)
        assert self._run([bundle, "--rid", "99"]) == 2

    def test_unreadable_source_exits_2(self, tmp_path, capsys):
        assert self._run([str(tmp_path / "missing.json")]) == 2
        assert "cannot read" in capsys.readouterr().err

    def test_trace_dir_ingest_from_mirrored_jsonl(self, tmp_path, capsys):
        """`record_span` mirrors into an armed spans JSONL writer; the dir
        form of `atx trace` must reassemble the same per-request view."""
        from accelerate_tpu.telemetry import spans as spans_mod

        d = tmp_path / "tracedir"
        d.mkdir()
        spans_mod.start_trace_log(str(d / "spans_0.jsonl"))
        try:
            self._bundle(tmp_path)  # records through the mirror too
        finally:
            spans_mod.stop_trace_log()
        assert self._run([str(d), "--check", "0.05", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert sorted(payload["requests"]) == ["0", "1"]


# ------------------------------------------------- SystemExit JSONL flush
class TestAtexitFlush:
    @pytest.mark.parametrize("exit_style", ["systemexit", "exit75"])
    def test_spans_jsonl_survives_abrupt_exit(self, tmp_path, exit_style):
        """Satellite 1: a process that dies via SystemExit (incl. the
        exit-75 preemption path) must leave a complete, parseable spans
        JSONL behind — the atexit hook flushes and fsyncs the writer."""
        path = str(tmp_path / "spans.jsonl")
        code = 75 if exit_style == "exit75" else 3
        child = (
            "import sys\n"
            "from accelerate_tpu.telemetry import spans, flight\n"
            f"spans.start_trace_log({path!r})\n"
            "for i in range(50):\n"
            "    flight.record_span('phase_decode', rid=i, t0=1.0, t1=2.0)\n"
            f"raise SystemExit({code})\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", child],
            cwd=REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == code, proc.stderr
        with open(path) as f:
            events = [json.loads(line) for line in f if line.strip()]
        assert len(events) == 50
        assert all(e["ph"] == "X" and e["name"] == "phase_decode" for e in events)
