"""The readers of what the program says about itself (ISSUE 27), on two
small traces recorded on a TPU v5e by PR 27's own chip runs of this
benchmark and cut, event for event, as the two older ones were (names,
starts and lengths as recorded; only the lines the reductions read; host
spans that straddle the cut's ends clipped to it; the program's spans keep
their stats):

- ``long_named_decode_prefill``: 0.55 s of `mistral7b-serve-long` - a decode
  step, a 256-row prefill chunk, two decode steps, a 1024-row chunk and a
  decode step, the tail of an earlier 1024-row chunk before them;
- ``train_named_two_steps``: two whole steps of `mistral7b-train-1chip`.

The expected values were counted by hand from the events (sums of the
kernels' own lengths inside each program, differences of span ends), not
with the code under test.
"""

import gzip
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _modules():
    """Imported when a test runs, not when this file is collected:
    `test_by_name.py` takes every `benchmarks` module out of `sys.modules`,
    and a module object from before that is not the one the readers see."""
    from benchmarks import harness
    from benchmarks.trace import program, xplane

    return harness, program, xplane


def _reading(name, cell_name, tmp_path, monkeypatch, outcome=None):
    """A `Reading` whose capture lies where the harness leaves a run's."""
    harness, _, xplane = _modules()
    log_dir = tmp_path / cell_name / "plugins" / "profile" / "recorded"
    log_dir.mkdir(parents=True)
    path = log_dir / (name + ".xplane.pb")
    with gzip.open(os.path.join(HERE, name + ".xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    bench = harness.benchmark_file()
    entry, cell, config = harness.find_cell(bench, cell_name)
    return harness.Reading(
        outcome=outcome or {"counters": {}, "samples": {}}, trace=xplane.load(str(path)), spans=None,
        cell=cell, config=config, peaks=harness.device_peaks("TPU v5 lite"), chips=entry["chips"],
    )


@pytest.fixture
def long(tmp_path, monkeypatch):
    return _reading("long_named_decode_prefill", "mistral7b-serve-long", tmp_path, monkeypatch)


@pytest.fixture
def train(tmp_path, monkeypatch):
    return _reading("train_named_two_steps", "mistral7b-train-1chip", tmp_path, monkeypatch)


def _read(metric, reading, **override):
    harness = _modules()[0]
    read, args = harness.load_reader(metric)
    return read(reading, **{**args, **override})


def test_kernels_carry_their_names(long, train):
    _, program, xplane = _modules()
    names = lambda r: {program.kernel_of(n) for n, _, _ in r.trace.devices[0].ops} - {None}
    assert names(long) == {"int8_matmul", "flash_decode"}
    assert names(train) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # what PR 25's traces show: a kernel with empty metadata, and no kernel
    assert program.kernel_of(
        '%closed_call.29 = bf16[32,1024]{1,0} custom-call(bf16[32,4096]{1,0} %x), '
        'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
    ) == ""
    assert program.kernel_of('%custom-call.6 = bf16[8]{0} custom-call(), custom_call_target="AllocateBuffer"') is None


def test_program_spans_are_read_with_their_stats(long):
    _, program, xplane = _modules()
    spans = program.spans_of(long)
    steps = [s.name for s in spans if s.name in program.STEP_SPANS]
    assert steps == ["serve_decode", "serve_prefill", "serve_decode", "serve_decode", "serve_prefill", "serve_decode"]
    dispatches = [s.stats for s in spans if s.name == "serve_dispatch"]
    assert dispatches == [
        {"bucket": 256, "slot": 1, "rid": 15}, {"resident": 4, "block": 1}, {"resident": 4, "block": 1},
        {"bucket": 1024, "slot": 3, "rid": 16}, {"resident": 3, "block": 1},
    ]
    # inside the benchmark's own engine-step spans, on the same clock
    outer = [(s, e) for n, s, e in long.trace.host if n == "engine-step"]
    for span in spans:
        assert any(s <= span.start and span.end <= e for s, e in outer), span


def test_kernel_time_inside_a_decode_step(long):
    # four whole decode steps: 32 flash_decode and 224 int8_matmul calls each
    flash = _read("flash_decode_device_ms.chat", long)
    int8 = _read("int8_matmul_device_ms.chat", long)
    outside = _read("decode_outside_kernels_ms.chat", long)
    assert flash == pytest.approx((8.640083 + 8.648687) / 2, abs=1e-4)
    assert int8 == pytest.approx((10.307725 + 10.319894) / 2, abs=1e-4)
    # three readers, one step: a kernel left unnamed would show here
    step = _read("decode_step_device_ms.chat", long)
    assert flash + int8 + outside == pytest.approx(step, rel=2e-3)
    assert outside == pytest.approx(50.6, abs=0.3)


def test_prefill_chunks_pair_with_their_dispatch_spans(long):
    _, program, xplane = _modules()
    spans = program.spans_of(long)
    pairs = program.pair_dispatches(
        [s for s in spans if s.name == "serve_dispatch" and "bucket" in s.stats],
        xplane.matching(long.trace.devices[0].modules, "^jit_prefill_fn"),
        [s for s in spans if s.name == "serve_fetch"],
    )
    # the chunk that was running when the cut began has no span in hand
    assert [(s.stats["bucket"], round((e - b) * 1e3, 1)) for s, (_, b, e) in pairs] == [(256, 68.8), (1024, 189.7)]
    assert _read("prefill_chunk_device_ms.long.b1024", long) == pytest.approx(189.7, abs=0.05)
    assert _read("prefill_chunk_device_ms.long.b1024", long, bucket=256) == pytest.approx(68.8, abs=0.05)
    assert _read("prefill_chunk_device_ms.long", long) == pytest.approx((68.81 + 189.71) / 2, abs=0.05)  # a median over buckets
    assert _read("int8_matmul_device_ms.long", long) == pytest.approx(65.080132, abs=1e-4)
    assert _read("int8_matmul_device_ms.long", long, bucket=256) == pytest.approx(17.94932, abs=1e-4)


def test_engine_step_host_time_leaves_the_fetch_out(long):
    # span minus its serve_fetch child, step by step (ms): the first is cut by the window
    by_hand = sorted([71.7262 - 71.6386, 71.0681 - 69.8515, 71.7003 - 70.6587, 71.5443 - 70.6864, 1.0568, 260.4158 - 259.5058])
    assert _read("engine_step_host_ms.long", long) == pytest.approx((by_hand[2] + by_hand[3]) / 2, abs=2e-4)
    assert _read("engine_step_host_ms.chat", long) == _read("engine_step_host_ms.long", long)


def test_flash_forward_time_inside_a_train_step(train):
    _, program, xplane = _modules()
    # 24 flash_fwd calls a step: 12 layers, forward and its remat replay
    assert _read("flash_fwd_device_ms.train", train) == pytest.approx((32.600843 + 32.593191) / 2, abs=1e-4)
    assert _read("flash_fwd_device_ms.train", train, kernels="^flash_bwd_dkv$") == pytest.approx(27.8467, abs=1e-3)
    assert [s.stats for s in program.spans_of(train)] == [{"step": 21}, {"step": 22}]


@pytest.mark.parametrize("recorded, cell", [
    ("chat_decode_prefill_decode", "mistral7b-serve-chat"), ("train_two_steps", "mistral7b-train-1chip"),
])
def test_a_program_that_says_nothing_reads_as_nothing(recorded, cell, tmp_path, monkeypatch):
    """PR 25's traces are the parent's: unnamed kernels, no program span.
    Every new reader gives ``None`` there and none raises - but for the time
    outside kernels, which needs no name."""
    reading = _reading(recorded, cell, tmp_path, monkeypatch, outcome={
        "counters": {}, "samples": {}, "t_open": 100.0, "t_close": 130.0,
    })
    harness = _modules()[0]
    bench = harness.benchmark_file()
    new = [m["name"] for m in harness.metrics_of(bench, cell, "per_layer")][-9:]
    values = {m: _read(m, reading) for m in new if m in {
        "flash_decode_device_ms.chat", "int8_matmul_device_ms.chat", "decode_outside_kernels_ms.chat",
        "engine_step_host_ms.chat", "queue_wait_ms.chat", "flash_fwd_device_ms.train"}}
    assert values
    outside = values.pop("decode_outside_kernels_ms.chat", None)
    assert all(v is None for v in values.values()), values
    if cell == "mistral7b-serve-chat":
        assert outside == pytest.approx(88.2 * 0.77, rel=0.02)  # PERF.md: 77% of a decode step is not kernels
    reading.trace = None  # an untraced run
    assert all(_read(m, reading) is None for m in values)


def test_queue_wait_is_read_from_the_request_records(tmp_path, monkeypatch):
    from accelerate_tpu.telemetry import flight

    reading = _reading("chat_decode_prefill_decode", "mistral7b-serve-chat", tmp_path, monkeypatch, outcome={
        "counters": {}, "samples": {}, "t_open": 100.0, "t_close": 130.0,
    })
    flight.reset_recorder()
    try:
        def request(rid, submitted, started):
            flight.record_span("request", rid=rid, t0=submitted, t1=started + 5.0, prefill_started_at=started)

        request(0, 99.0, 99.1)      # submitted before the window opened
        request(1, 101.0, 101.05)   # 50 ms
        request(2, 110.0, 110.15)   # 150 ms
        request(3, 111.9, 112.4)    # started after the profiler did (100 + 0.4 x 30 = 112)
        request(4, 120.0, 0.0)      # cancelled in the queue
        flight.record_span("phase_queue", rid=1, t0=101.0, t1=101.05)
        assert _read("queue_wait_ms.chat", reading) == pytest.approx(100.0)
    finally:
        flight.reset_recorder()
