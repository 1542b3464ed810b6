"""A later PR adds a configuration, a cell, a traffic kind and a per-layer
metric as new files plus entries in `BENCHMARK.json`, and edits no file
that is there: shown on a temporary copy of the benchmark."""

import importlib
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def copy(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "*.gz"),
    )
    return tmp_path


def _snapshot(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = fh.read()
    return out


def test_new_files_are_found_by_name_without_editing_any(copy):
    from benchmarks import harness

    before = _snapshot(copy / "benchmarks")
    b = copy / "benchmarks"
    # a configuration
    config = json.loads((b / "configs" / "mistral-7b-v0.3.json").read_text())
    config.update(name="mistral-7b-v0.3-8l", num_hidden_layers=8, reduced=["num_hidden_layers"])
    (b / "configs" / "mistral-7b-v0.3-8l.json").write_text(json.dumps(config))
    # a traffic kind (new code is allowed to a PR that claims no gain in it)
    (b / "traffic" / "burst_gamma.py").write_text(
        "from .open_loop_poisson import SYSTEM, schedule  # noqa: F401\n"
    )
    # a cell
    cell = json.loads((b / "workloads" / "mistral7b-serve-chat.json").read_text())
    cell.update(name="mistral7b-serve-burst", config="mistral-7b-v0.3-8l")
    cell["traffic"]["kind"] = "burst_gamma"
    (b / "workloads" / "mistral7b-serve-burst.json").write_text(json.dumps(cell))
    # a per-layer metric: one with a shared reader, one with a reader of its own
    (b / "metrics" / "ttft_p99_ms.burst.json").write_text(
        json.dumps({"reader": "percentile", "args": {"of": "ttft_ms", "q": 99}})
    )
    (b / "metrics" / "queue_share.burst.json").write_text(json.dumps({"reader": "own", "args": {"k": 2}}))
    (b / "metrics" / "queue_share.burst.py").write_text(
        "def read(reading, k):\n    return k * reading.outcome['counters']['admitted']\n"
    )
    # the entries
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mistral-7b-v0.3-8l", "source": config["source"],
        "file": "benchmarks/configs/mistral-7b-v0.3-8l.json", "reduced": ["num_hidden_layers"], "why": "test",
    })
    bench["workloads"].append({
        "name": "mistral7b-serve-burst", "config": "mistral-7b-v0.3-8l", "traffic": "burst", "chips": 1, "why": "test",
    })
    for name in ("ttft_p99_ms.burst", "queue_share.burst"):
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower", "source": "host_clock",
            "layer": "engine scheduler", "moves": "itl_p95_ms", "workloads": ["mistral7b-serve-burst"],
        })
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    # found by name, from the copy
    entry, found_cell, found_config = harness.find_cell(harness.benchmark_file(str(copy)), "mistral7b-serve-burst", str(copy))
    assert entry["chips"] == 1 and found_config["num_hidden_layers"] == 8
    assert found_cell["traffic"]["kind"] == "burst_gamma"
    names = [m["name"] for m in harness.metrics_of(json.loads((copy / "BENCHMARK.json").read_text()), "mistral7b-serve-burst", "per_layer")]
    assert names == ["ttft_p99_ms.burst", "queue_share.burst"]  # and none of another cell's
    reading = harness.Reading(
        outcome={"samples": {"ttft_ms": [1.0, 2.0, 3.0]}, "counters": {"admitted": 21}},
        trace=None, spans=None, cell=found_cell, config=found_config, peaks={}, chips=1,
    )
    read, args = harness.load_reader("ttft_p99_ms.burst", str(b))
    assert read(reading, **args) == pytest.approx(2.98)
    read, args = harness.load_reader("queue_share.burst", str(b))
    assert read(reading, **args) == 42
    # the new traffic kind imports from the copy
    sys.path.insert(0, str(copy))
    try:
        for mod in [m for m in sys.modules if m == "benchmarks" or m.startswith("benchmarks.")]:
            sys.modules.pop(mod)
        kind = importlib.import_module("benchmarks.traffic.burst_gamma")
        assert kind.__file__.startswith(str(copy)) and kind.SYSTEM == "engine"
    finally:
        sys.path.remove(str(copy))
        for mod in [m for m in sys.modules if m == "benchmarks" or m.startswith("benchmarks.")]:
            sys.modules.pop(mod)
    # and no file that was there has changed
    after = _snapshot(copy / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_entry_of_the_benchmark_has_its_files():
    from benchmarks import harness

    bench = harness.benchmark_file()
    for w in bench["workloads"]:
        entry, cell, config = harness.find_cell(bench, w["name"])
        assert cell["name"] == w["name"] and cell["config"] == w["config"] == config["name"]
        importlib.import_module("benchmarks.traffic." + cell["traffic"]["kind"])
        reported = {k: [m["name"] for m in harness.metrics_of(bench, w["name"], k)] for k in ("end_to_end", "per_layer")}
        assert "setup_s" in reported["end_to_end"] and len(reported["end_to_end"]) >= 2
        assert reported["per_layer"]
        for m in harness.metrics_of(bench, w["name"], "per_layer"):
            assert m["moves"] in reported["end_to_end"], (w["name"], m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        read, args = harness.load_reader(m["name"])
        assert callable(read)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["qwen7b-train-4chip"]
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"] and config["reduced"] == c["reduced"] and "assumed" in config
