"""The cell `olmohybrid-serve-chat`: its traffic kind, its files found by
name, the arithmetic its readers divide by, and the whole cell walked at
tiny widths on the CPU (the rehearsal)."""

import json

import numpy as np
import pytest

from benchmarks import flops_gdn, harness
from benchmarks.traffic import open_loop_poisson, open_loop_poisson_hybrid

CELL = "olmohybrid-serve-chat"
METRICS = [
    "decode_step_device_ms.hybrid", "prefill_chunk_device_ms.hybrid.b256", "gdn_decode_device_ms.hybrid",
    "flash_decode_device_ms.hybrid", "gdn_chunk_device_ms.hybrid.b256", "gdn_decode_roofline.hybrid",
    "gdn_chunk_roofline.hybrid.b256", "decode_step_roofline.hybrid", "state_dead_share.hybrid",
    "kv_dead_rows_share.hybrid", "slots_busy_share.hybrid", "host_gap_ms.hybrid", "engine_step_host_ms.hybrid",
    "decode_outside_kernels_ms.hybrid",
]


@pytest.fixture(scope="module")
def found():
    bench = harness.benchmark_file()
    return (bench, *harness.find_cell(bench, CELL))


def test_the_cell_is_what_the_issue_set(found):
    bench, entry, cell, config = found
    assert entry == {**entry, "config": "olmo-hybrid-7b-16l", "traffic": "chat-poisson-400", "chips": 1}
    engine = {k: v for k, v in cell["engine"].items() if k != "note"}
    assert engine == {
        "weights": "bf16", "slots": 32, "max_len": 2048, "buckets": [64, 128, 256],
        "prefill_interleave": 1, "decode_block": 1, "prefix_cache": False,
    }
    traffic = dict(cell["traffic"])
    assert isinstance(traffic.pop("rate"), float)
    assert traffic == {
        "kind": "open_loop_poisson_hybrid",
        "prompt_tokens": {"dist": "lognormal", "median": 400, "sigma": 0.8, "min": 32, "max": 1536},
        "new_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.5, "min": 16, "max": 384},
        "warm_seconds": 5.0, "cool_seconds": 45.0, "drain_seconds": 45.0, "schedule_seed": 34,
    }
    assert cell["probe"]["prompt_tokens"] == [50, 100, 250, 257, 300, 700, 1500] and cell["probe"]["new_tokens"] == 32
    assert cell["trace"] == {"start_share": 0.4, "seconds": 2.0}
    assert [m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")] == ["itl_p95_ms", "setup_s"]
    assert config["num_hidden_layers"] == 16 and len(config["layer_types"]) == 32


@pytest.mark.parametrize("metric", METRICS)
def test_every_per_layer_metric_of_the_cell_has_its_files(found, metric):
    bench = found[0]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL] and entry["moves"] == "itl_p95_ms"
    read, args = harness.load_reader(metric)
    # A program without the counters and a run without a trace (the parent of
    # the PR that added them): the reader gives nothing and does not raise.
    bare = harness.Reading(
        outcome={"counters": {"decode_steps": 0, "slots": 32}, "samples": {}}, trace=None, spans=None,
        cell=found[2], config=found[3], peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, chips=1,
    )
    assert read(bare, **args) is None


def test_the_traffic_kind_replays_the_chat_schedule_on_the_hybrid_system(found):
    params = found[2]["traffic"]
    assert open_loop_poisson_hybrid.SYSTEM == "engine_olmo_hybrid"
    assert open_loop_poisson_hybrid.schedule is open_loop_poisson.schedule
    a = open_loop_poisson_hybrid.schedule(params, 30.0)
    b = open_loop_poisson_hybrid.schedule(params, 30.0)
    rows = lambda s: [(j.due, j.prompt_tokens, j.new_tokens, j.phase) for j in s.initial]
    assert rows(a) == rows(b) and a.measured_by == "due"
    window = [j for j in a.initial if j.phase == "window"]
    assert len(window) == round(params["rate"] * 30.0)
    prompts = np.array([j.prompt_tokens for j in a.initial])
    news = np.array([j.new_tokens for j in a.initial])
    assert prompts.min() >= 32 and prompts.max() <= 1536 and news.min() >= 16 and news.max() <= 384
    assert (prompts + news).max() <= 1920 < found[2]["engine"]["max_len"]
    assert 350 <= np.median([j.prompt_tokens for j in window]) <= 450
    # every prompt's bucket-padded plan fits a slot: no request is refused
    assert (-(-prompts // 64) * 64).max() <= found[2]["engine"]["max_len"]


def test_the_arithmetic_of_the_configuration(found):
    config = found[3]
    assert flops_gdn.linear_layers(config) == 12 and flops_gdn.full_layers(config) == 4
    assert flops_gdn.linear_layer_params(config) == pytest.approx(215.6e6, rel=1e-3)
    assert flops_gdn.full_layer_params(config) == pytest.approx(185.8e6, rel=1e-3)
    assert flops_gdn.state_bytes(config) == 30 * 96 * 192 * 4 and flops_gdn.conv_tail_bytes(config) == 3 * 11520 * 2
    assert flops_gdn.kv_row_bytes(config) * 4 == 61_440
    weights = flops_gdn.decode_step_bytes(config, 0, 0)
    assert weights == pytest.approx(2 * (4100.79e6 - 100352 * 3840), rel=1e-3)  # all but the embedding
    full = flops_gdn.decode_step_bytes(config, 32 * 12, 32 * 1000)
    assert full - weights == 32 * 12 * 2 * (2_211_840 + 69_120) + 32 * 1000 * 61_440
    assert flops_gdn.gdn_decode_bytes(config, 1) == 2 * 2_211_840 + 30 * (2 * 96 + 2 * 192 + 2) * 4
    per_head = 6 * 64 * 96 * 192 + 64 * 64 * 192
    assert flops_gdn.chunk_state_pass_flops(config, 256) == 12 * 30 * 4 * per_head
    assert flops_gdn.chunk_form_flops(config, 256) == 12 * 30 * 4 * (per_head + 64 * 64 * (3 * 96 + 192))


def test_the_new_readers_divide_the_counts_by_what_ran(found, monkeypatch):
    """The three roofline readers on a reading with counts and a kernel time:
    least bytes (or operations) over the peak over the time, in per cent."""
    from benchmarks.metrics.readers import kernel_device, program_device

    config = found[3]
    monkeypatch.setattr(kernel_device, "read", lambda reading, programs, kernels, bucket=None: 2.0)
    monkeypatch.setattr(program_device, "read", lambda reading, programs: 16.0)
    counters = {"decode_steps": 10, "state_slots_live": 10 * 20 * 12, "kv_rows_live_full": 10 * 20 * 600,
                "state_rows_real": 800, "state_rows_padded": 1024}
    reading = harness.Reading(
        outcome={"counters": counters}, trace=None, spans=None, cell=found[2], config=config,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, chips=1,
    )
    read, args = harness.load_reader("gdn_decode_roofline.hybrid")
    assert read(reading, **args) == pytest.approx(100 * flops_gdn.gdn_decode_bytes(config, 240) / 819e9 / 2e-3)
    read, args = harness.load_reader("decode_step_roofline.hybrid")
    want = 100 * flops_gdn.decode_step_bytes(config, 240, 12000) / 819e9 / 16e-3
    assert read(reading, **args) == pytest.approx(want) and 50 < want < 100
    read, args = harness.load_reader("gdn_chunk_roofline.hybrid.b256")
    assert read(reading, **args) == pytest.approx(100 * flops_gdn.chunk_state_pass_flops(config, 200) / 197e12 / 2e-3)
    read, args = harness.load_reader("state_dead_share.hybrid")
    assert read(harness.Reading({"counters": {"state_slots_live": 30, "state_slots_touched": 40}}, None, None, {}, {}, {}, 1), **args) == 25.0


def test_the_state_comparison_is_a_norm_over_the_reference_layer_by_layer():
    """`state_distances` on made-up states: the norm of the difference over
    the reference's norm, pooled over the prompts, a layer and a phase at a
    time; a reference with fewer layers is held against those it has."""
    from benchmarks.systems.engine_olmo_hybrid import state_distances

    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(3, 2, 2, 4, 8)).astype(np.float32) for _ in range(2)]  # (layers, phases, H, dk, dv)
    assert state_distances(ref, ref) == {"state_rel_after_prefill": [0.0] * 3, "state_rel_after_decode": [0.0] * 3}
    off = [a.copy() for a in ref]
    for a in off:
        a[1, 0] *= 1.01  # layer 1 after the prompt, a hundredth off
        a[2, 1] *= 0.98  # layer 2 after the decode steps, a fiftieth
    d = state_distances(off, ref)
    assert d["state_rel_after_prefill"] == pytest.approx([0.0, 0.01, 0.0], abs=1e-6)
    assert d["state_rel_after_decode"] == pytest.approx([0.0, 0.0, 0.02], abs=1e-6)
    fewer = state_distances(off, [a[:2] for a in ref])
    assert len(fewer["state_rel_after_prefill"]) == 2 and fewer["state_rel_after_decode"] == [0.0, 0.0]


@pytest.mark.parametrize(
    "change,ok",
    [
        ({}, True),
        ({"state_rel_after_prefill": [3.1e-3, 6e-3]}, False),  # a bf16 pass over the state in the chunk kernel
        ({"state_rel_after_decode": [7.2e-3, 9e-3]}, False),  # a state stored in bf16
        ({"state_rel_after_decode": [float("nan"), 9e-3]}, False),
        ({"state_rel_after_prefill": [3e-4, 0.5]}, True),  # only the first linear layer is judged
        ({"mean_short_of_top": 1.75e-2}, False),  # fp8 weights
        ({"exact_argmax_share": 0.6}, False),
        ({"wrong_length": [3]}, False),
    ],
)
def test_the_probe_is_judged_on_the_logits_and_on_the_first_layers_state(change, ok):
    from benchmarks.systems.engine_olmo_hybrid import TOLERANCES, within

    shipped = {
        "mean_short_of_top": 2e-4, "exact_argmax_share": 0.95, "wrong_length": [],
        "state_rel_after_prefill": [3.3e-4, 5e-3], "state_rel_after_decode": [2.2e-3, 5.5e-3],
    }
    assert within({**shipped, **change}, TOLERANCES) is ok


def test_the_cell_rehearses_on_the_cpu(capsys):
    """The whole cell at tiny widths: build, probe against the reference,
    traffic of the new kind, the window's invariants and counters."""
    from benchmarks.systems import engine_olmo_hybrid

    assert engine_olmo_hybrid.main(["--rehearse", "--seconds", "1.5", "--seeds", "7"]) == 0
    out = capsys.readouterr().out
    (last,) = [l for l in out.splitlines() if l.startswith("REHEARSAL ")]
    line = json.loads(last[len("REHEARSAL "):])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    (window,) = [json.loads(l.split(": ", 1)[1]) for l in out.splitlines() if l.startswith("[bench] window:")]
    assert window["invariants"]["probe_within_bf16_tolerance"] is True
    assert window["compilations_in_window"] == 0
    c = window["counters"]
    assert c["state_slots_live"] == 3 * c["decode_slot_steps"] > 0
    assert c["state_rows_padded"] >= c["state_rows_real"] == c["prompt_tokens"] and c["state_resets"] == c["admitted"]
