"""The trace reduction on two small recorded traces.

Both were recorded on a TPU v5e by PR 25's own chip runs of this benchmark
and cut, event for event, to a short stretch (the cut keeps names, starts
and lengths as recorded, drops every line the reduction does not read, and
clips the host spans that straddle its ends):

- ``train_two_steps``: the first two steps of a traced window of
  `mistral7b-train-1chip` (12 layers, 1 x 4096 tokens);
- ``chat_decode_prefill_decode``: a decode step, a prefill chunk and another
  decode step of `mistral7b-serve-chat` (32 slots x 1024).
"""

import gzip
import os

import pytest

from benchmarks.trace import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name, tmp_path):
    with gzip.open(os.path.join(HERE, name + ".xplane.pb.gz")) as f:
        path = tmp_path / (name + ".xplane.pb")
        path.write_bytes(f.read())
    return xplane.load(str(path))


@pytest.fixture
def train(tmp_path):
    return _load("train_two_steps", tmp_path)


@pytest.fixture
def chat(tmp_path):
    return _load("chat_decode_prefill_decode", tmp_path)


def test_train_programs_and_busy_time(train):
    (device,) = train.devices
    steps = xplane.matching(device.modules, r"^jit_step_fn")
    assert len(steps) == 2
    lo, hi = train.window
    assert hi - lo == pytest.approx(1.2486, abs=1e-3)
    # the device is busy for all but a millisecond of two 649 ms steps
    busy = xplane.busy_seconds(device)
    assert busy == pytest.approx(1.2486, abs=2e-3)
    assert 1 - busy / (hi - lo) < 0.002
    assert [round((e - s) * 1e3) for _, s, e in steps] == [600, 649]  # the first began before the cut
    inside = [xplane.busy_inside(device, (s, e)) for _, s, e in steps]
    assert inside[1] == pytest.approx(0.64900, abs=1e-4)
    (gap,) = xplane.gaps_between(steps)
    assert (gap[1] - gap[0]) * 1e6 == pytest.approx(4.75, abs=0.5)  # microseconds between two steps


def test_mfu_is_read_from_the_device_clock(train):
    from benchmarks import harness

    bench = harness.benchmark_file()
    _, cell, config = harness.find_cell(bench, "mistral7b-train-1chip")
    reading = harness.Reading(
        outcome={"counters": {"seq_len": 4096, "tokens_per_step": 4096}, "samples": {}},
        trace=train, spans=None, cell=cell, config=config,
        peaks=harness.device_peaks("TPU v5 lite"), chips=1,
    )
    read, args = harness.load_reader("mfu.train")
    # 17.72 GFLOP a token x 4096 tokens over 649.0 ms + 5 us, of 197 TFLOP/s
    assert read(reading, **args) == pytest.approx(56.76, abs=0.02)
    reading.trace = None  # an untraced run has nothing to read it from
    assert read(reading, **args) is None


def test_train_flash_kernels_are_the_custom_calls(train):
    (device,) = train.devices
    calls = xplane.matching(device.ops, 'custom_call_target="tpu_custom_call"')
    step = xplane.matching(device.modules, r"^jit_step_fn")[1]
    inside = [c for c in calls if c[1] >= step[1] and c[2] <= step[2]]
    assert len(inside) == 4 * 12  # forward, remat forward and two backward calls a layer
    total = sum(e - s for _, s, e in inside)
    assert total == pytest.approx(0.0820, abs=1e-3)  # 12.6% of the step
    assert xplane.exposed_collective_seconds(device) == 0.0  # one chip: no collective


def test_idle_gaps_go_to_the_host_span_that_covers_them(train):
    (device,) = train.devices
    gaps = xplane.idle_gaps(device, train.window)
    assert gaps and sum(b - a for a, b in gaps) < 2e-3
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert xplane.attribute(longest, train.host) == "block"
    assert xplane.attribute((10.0, 10.1), train.host) == "(no span)"
    out = xplane.breakdown(train)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"][0][0] == "block"
    assert all(len(label) < 130 and not label.startswith("while") for label, _ in out["device_ops"])
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1] > 0


def test_describe_reads_kind_and_target():
    kind, label = xplane.describe(
        '%closed_call.31 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(s32[32]{0} %x), '
        'custom_call_target="tpu_custom_call"'
    )
    assert kind == "custom-call" and label.endswith("[tpu_custom_call]") and "{" not in label
    kind, _ = xplane.describe("%while.19 = (s32[]{:T(128)}, bf16[1,4096]{1,0}) while((s32[]) %t), condition=%c")
    assert kind == "while"


def test_chat_decode_and_prefill_programs(chat):
    (device,) = chat.devices
    decode = xplane.whole(xplane.matching(device.modules, r"^jit_decode_fn"), chat.window)
    prefill = xplane.whole(xplane.matching(device.modules, r"^jit_prefill_fn"), chat.window)
    assert len(decode) == 2 and len(prefill) == 1
    assert [round((e - s) * 1e3, 1) for _, s, e in decode] == [88.2, 88.2]
    assert round((prefill[0][2] - prefill[0][1]) * 1e3, 1) == 34.1
    engine = xplane.matching(device.modules, r"^jit_(decode|prefill)_fn")
    gaps = [round((b - a) * 1e3, 2) for a, b in xplane.gaps_between(engine)]
    assert gaps == [2.9, 0.0]  # the host loop before a chunk; the next decode was already queued
    assert xplane.attribute(xplane.gaps_between(engine)[0], chat.host) == "engine-step"


def test_interval_arithmetic_on_a_made_up_device():
    device = xplane.Device(0, [], [
        ("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 0.0, 4.0),
        ("%fusion.1 = f32[8] fusion(f32[8] %x)", 1.0, 2.0),
        ("%all-gather.2 = f32[8] all-gather(f32[8] %x)", 6.0, 7.0),
        ("%fusion.2 = f32[8] fusion(f32[8] %x)", 6.5, 9.0),
    ])
    assert xplane.busy_seconds(device) == pytest.approx(7.0)
    # alone on the device: 0-1 and 2-4 of the all-reduce, 6-6.5 of the all-gather
    assert xplane.exposed_collective_seconds(device) == pytest.approx(3.5)
    assert xplane.idle_gaps(device, (0.0, 10.0)) == [(4.0, 6.0), (9.0, 10.0)]
    # an asynchronous collective counts from its start to its done, less what runs beside it
    device.async_ops = [("%all-gather-start.3 = (f32[8]) all-gather-start(f32[8] %x)", 7.0, 12.0)]
    assert xplane.exposed_collective_seconds(device) == pytest.approx(3.5 + 3.0)
    assert xplane.clip(device.ops, (1.5, 6.2))[0][1:] == (1.5, 4.0)
