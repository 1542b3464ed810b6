"""The readers that put the device's idle gaps down to the engine phase
the host was in (`gap_phase`) and a counted share of the window
(`counter_window_share`): on the recorded traces of
`test_program_trace.py`, and on a few intervals laid out by hand."""

import pytest

from benchmarks.tests.test_program_trace import _modules, _reading

GAP_METRICS = [f"gap_{part}_ms.{cell}" for cell in ("chat", "mixed", "hybrid") for part in ("fetch", "engine", "caller")]
PROGRAMS = "^jit_(decode|prefill)_fn"


@pytest.fixture
def long(tmp_path, monkeypatch):
    return _reading("long_named_decode_prefill", "mistral7b-serve-long", tmp_path, monkeypatch)


@pytest.fixture
def chat(tmp_path, monkeypatch):
    """A trace of a program from before the engine's spans."""
    return _reading("chat_decode_prefill_decode", "mistral7b-serve-chat", tmp_path, monkeypatch)


def _read(metric, reading):
    harness = _modules()[0]
    read, args = harness.load_reader(metric)
    return read(reading, **args)


def test_the_three_parts_add_up_to_the_mean_step_gap(long):
    _, _, xplane = _modules()
    gaps = xplane.gaps_between(xplane.matching(long.trace.devices[0].modules, PROGRAMS))
    assert len(gaps) >= 4
    # the gaps whose median host_gap_ms reads
    assert _read("host_gap_ms.long", long) == pytest.approx(xplane.median_ms([b - a for a, b in gaps]))
    mean_ms = 1e3 * sum(b - a for a, b in gaps) / len(gaps)
    for cell in ("chat", "mixed", "hybrid"):
        parts = [_read(f"gap_{part}_ms.{cell}", long) for part in ("fetch", "engine", "caller")]
        assert all(p >= 0.0 for p in parts), parts
        assert sum(parts) == pytest.approx(mean_ms, abs=1e-9)
    # the engine's own python is in every gap, and a fetch's tail in some
    assert _read("gap_engine_ms.chat", long) > 0.0 and _read("gap_fetch_ms.chat", long) > 0.0


def test_a_trace_without_the_programs_spans_reads_none(chat):
    assert chat.trace.devices and _modules()[1].spans_of(chat) == ()
    for metric in GAP_METRICS:
        assert _read(metric, chat) is None


def test_a_gap_is_split_by_the_span_that_covers_it(long, monkeypatch):
    """Two gaps laid out by hand (seconds): [10, 14) under a decode step
    whose fetch ends at 11 and which ends at 13, the caller's loop after;
    [20, 22) with no span, after a sleep left out of a third."""
    harness, program, xplane = _modules()
    spans = [
        program.Span("serve_decode", 5.0, 13.0, {}),
        program.Span("serve_fetch", 9.0, 11.0, {}),
        program.Span("serve_emit", 11.0, 12.5, {}),
    ]
    monkeypatch.setattr(program, "spans_of", lambda reading: tuple(spans))
    modules = [("jit_decode_fn(1)", 0.0, 10.0), ("jit_prefill_fn(2)", 14.0, 20.0),
               ("jit_decode_fn(1)", 22.0, 30.0), ("jit_decode_fn(1)", 31.0, 40.0)]
    long.trace = xplane.Trace(devices=[xplane.Device(0, modules, [])], host=[("sleep-until-due", 30.0, 31.0)],
                              window=(0.0, 40.0))
    # fetch 1 (10-11), engine 2 (11-13), caller 1 (13-14); the second gap is the caller's
    assert _read("gap_fetch_ms.mixed", long) == pytest.approx(1e3 * 1 / 2)
    assert _read("gap_engine_ms.mixed", long) == pytest.approx(1e3 * 2 / 2)
    assert _read("gap_caller_ms.mixed", long) == pytest.approx(1e3 * (1 + 2) / 2)
    read, args = harness.load_reader("gap_fetch_ms.mixed")
    with pytest.raises(ValueError, match="part"):
        read(long, **{**args, "part": "dispatch"})


@pytest.mark.parametrize(
    "counters, share",
    [
        ({"decode_steps": 10}, None),  # a program from before the counter
        ({"stalled_fetch_ms": 0.0}, 0.0),
        ({"stalled_fetch_ms": 240.0}, 0.8),  # 240 ms of a 30 s window
    ],
)
def test_the_stalled_share_of_the_window(long, counters, share):
    long.outcome = {"counters": counters, "samples": {"window_seconds": 30}}
    for cell in ("mixed", "long"):
        value = _read(f"stalled_fetch_share.{cell}", long)
        assert value is None if share is None else value == pytest.approx(share)
