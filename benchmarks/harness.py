"""Runs one cell once and prints the contract's last line.

Everything that belongs to one configuration, one traffic mix or one metric
is found by its name in `BENCHMARK.json`:

- the configuration's file is the entry's ``file``;
- the cell's file is ``benchmarks/workloads/<cell>.json``; its ``traffic.kind``
  names the generator ``benchmarks/traffic/<kind>.py``, whose ``SYSTEM`` names
  the driver ``benchmarks/systems/<system>.py``;
- a metric is ``benchmarks/metrics/<metric>.json`` (``reader`` + ``args``),
  read by ``benchmarks/metrics/<metric>.py`` if that exists and else by
  ``benchmarks/metrics/readers/<reader>.py``.

So a later PR adds a configuration, a cell, a traffic kind or a metric as
new files plus entries, and edits no file that is here.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from typing import Any

from . import correctness, program
from .spans import WINDOW_SPAN, Spans
from .stats import median, percentile
from .trace import xplane

TRACE_DIR = os.path.join(program.REPO, ".bench_trace")


def say(topic: str, **fields: Any) -> None:
    """An earlier line of standard output, for a reader; the driver reads
    only the last."""
    print(f"[bench] {topic}: " + json.dumps(fields, default=_plain), flush=True)


def _plain(x: Any) -> Any:
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


# ------------------------------------------------------------------ by name
def benchmark_file(root: str = program.REPO) -> dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, root: str = program.REPO) -> tuple[dict, dict, dict]:
    """(entry of `workloads`, the cell's file, the configuration's file)."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json (has {sorted(entries)})")
    entry = entries[name]
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks", "workloads", name + ".json")) as f:
        cell = json.load(f)
    return entry, cell, config


def metrics_of(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics that ``cell_name`` reports."""
    return [m for m in bench[kind] if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(metric: str, root: str = program.ROOT):
    """(read function, args) for a metric, by name."""
    with open(os.path.join(root, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    own = os.path.join(root, "metrics", metric + ".py")
    if os.path.exists(own):  # a metric's name may hold dots: load it by path
        module_spec = importlib.util.spec_from_file_location(
            "benchmarks.metrics." + re.sub(r"\W", "_", metric), own
        )
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
    else:
        module = importlib.import_module("benchmarks.metrics.readers." + spec["reader"])
    return module.read, spec.get("args", {})


# ------------------------------------------------------------------- tracing
class Tracer:
    """Turns the profiler on for ``seconds`` of the window, ``start_at``
    seconds in. The traced stretch is marked by a `bench-window` span."""

    def __init__(self, enabled: bool, start_at: float, seconds: float, log_dir: str, spans: Spans):
        self.enabled, self.start_at, self.seconds = enabled, start_at, seconds
        self.log_dir, self.spans = log_dir, spans
        self.live, self.done, self._marker = False, not enabled, None

    def poll(self, rel: float) -> None:
        if self.done:
            return
        if not self.live and rel >= self.start_at:
            self._start()
        elif self.live and rel >= self.start_at + self.seconds:
            self._stop()

    def finish(self) -> None:
        if self.live:
            self._stop()

    def _start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's spans, not every Python call
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.live = True
        self.spans.annotate = True
        self._marker = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._marker.__enter__()

    def _stop(self) -> None:
        import jax

        self._marker.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.live, self.done = False, True


# --------------------------------------------------------------------- a run
@dataclasses.dataclass
class Context:
    name: str
    entry: dict
    cell: dict
    config: dict
    seed: int
    devices: list
    spans: Spans
    traffic_module: Any


@dataclasses.dataclass
class Reading:
    """What a metric's reader may look at."""

    outcome: dict
    trace: Any  # trace.xplane.Trace or None
    spans: Spans
    cell: dict
    config: dict
    peaks: dict
    chips: int


def device_peaks(kind: str) -> dict:
    table = program.load_json("peaks.json")
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in benchmarks/peaks.json")
    return table[kind]


def build_cell(ctx: Context):
    system = importlib.import_module("benchmarks.systems." + ctx.traffic_module.SYSTEM)
    return system.CELL(ctx)


def prepare(name: str, seed: int, *, rehearsal: bool = False, shrink=None) -> Context:
    """Everything up to (not including) touching the program: files found
    by name, devices checked, compile cache placed. ``shrink(cell, config)``
    (rehearsals only) cuts both to a size a CPU runs."""
    bench = benchmark_file()
    entry, cell, config = find_cell(bench, name)
    if shrink is not None:
        shrink(cell, config)
    import jax

    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu":
            print(f"bench: no TPU (JAX reports {devices[0].platform!r}); nothing was run", file=sys.stderr)
            raise SystemExit(2)
        if len(devices) < entry["chips"]:
            print(f"bench: {name} needs {entry['chips']} chips, JAX reports {len(devices)}", file=sys.stderr)
            raise SystemExit(2)
    from accelerate_tpu.state import configure_compile_cache

    cache_dir = configure_compile_cache()
    # The reference is many small programs: keep them too, so that a warm
    # run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    traffic_module = importlib.import_module("benchmarks.traffic." + cell["traffic"]["kind"])
    say("start", workload=name, seed=seed, device_kind=devices[0].device_kind,
        devices=len(devices), chips=entry["chips"], jax=jax.__version__, compile_cache_dir=cache_dir,
        rehearsal=rehearsal)
    return Context(
        name=name, entry=entry, cell=cell, config=config, seed=seed,
        devices=list(devices[: entry["chips"]]), spans=Spans(),
        traffic_module=traffic_module,
    )


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float, *,
             shrink=None, tolerances=correctness.TOLERANCES) -> dict:
    """``shrink`` makes it a rehearsal: any backend, tiny sizes, its own
    ``tolerances``, and a last line that says so."""
    rehearsal = shrink is not None
    ctx = prepare(name, seed, rehearsal=rehearsal, shrink=shrink)
    cell = build_cell(ctx)
    t0 = time.perf_counter()
    cell.build()
    t1 = time.perf_counter()
    distances = cell.probe()
    probe_ok = correctness.judge(ctx.traffic_module.SYSTEM, distances, tolerances)
    say("probe", correct=probe_ok, tolerances=tolerances, **distances)
    t2 = time.perf_counter()
    cell.warm()
    t3 = time.perf_counter()

    log_dir = os.path.join(TRACE_DIR, name)
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        os.makedirs(log_dir, exist_ok=True)
    tracing = ctx.cell["trace"]
    tracer = Tracer(trace, tracing["start_share"] * seconds, min(tracing["seconds"], seconds), log_dir, ctx.spans)
    outcome = cell.window(seconds, tracer)
    outcome["setup_s"] = outcome["t_open"] - t_start
    say("setup", setup_s=outcome["setup_s"], imports_s=t0 - t_start, build_s=t1 - t0,
        probe_s=t2 - t1, warm_s=t3 - t2, before_window_s=outcome["t_open"] - t3)
    say("window", attempted=outcome["attempted"], failed=outcome["failed"],
        invariants=outcome["invariants"], compilations_in_window=outcome["compilations_in_window"],
        counters=outcome["counters"],
        **{k: v for k, v in outcome["samples"].items() if not isinstance(v, list)},
        **{"n_" + k: len(v) for k, v in outcome["samples"].items() if isinstance(v, list)})
    if "losses" in outcome["samples"]:
        say("losses", losses=[round(x, 4) for x in outcome["samples"]["losses"]])

    kind = ctx.devices[0].device_kind
    # A rehearsal walks the arithmetic with the v5e's row; its values are not shown.
    peaks = device_peaks("TPU v5 lite" if rehearsal else kind)
    reduced = None
    if trace:
        t4 = time.perf_counter()
        reduced = xplane.load(xplane.find_xplane(log_dir))
        say("trace", read_s=time.perf_counter() - t4, devices=len(reduced.devices),
            window_s=reduced.window[1] - reduced.window[0],
            modules=sorted({n for d in reduced.devices for n, _, _ in d.modules})[:20])
    reading = Reading(outcome=outcome, trace=reduced, spans=ctx.spans, cell=ctx.cell,
                      config=ctx.config, peaks=peaks, chips=ctx.entry["chips"])
    metrics = {}
    for m in metrics_of(benchmark_file(), name, "per_layer" if trace else "end_to_end"):
        read, args = load_reader(m["name"])
        value = read(reading, **args)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # Beside the judged numbers, for a reader: each timing's median and count.
    for key, values in outcome["samples"].items():
        if isinstance(values, list) and values and key != "losses" and not rehearsal:
            say("samples", of=key, n=len(values), mean=sum(values) / len(values), median=median(values),
                p95=percentile(values, 95), max=max(values))
    in_window = [r for r in ctx.spans.records if r[1] >= outcome["t_open"]]
    say("longest_host_spans", spans=[
        {"span": n, "ms": (e - s) * 1e3, "at_s": s - outcome["t_open"]}
        for n, s, e in sorted(in_window, key=lambda r: r[1] - r[2])[:5]
    ])

    peak = 0
    for d in ctx.devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device = {
        "platform": ctx.devices[0].platform,
        "kind": kind,
        "count": len(ctx.devices),
        "memory_peak_bytes": peak,
    }
    line = {
        "correct": bool(probe_ok and all(outcome["invariants"].values())),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        busy = [xplane.busy_seconds(d) for d in reduced.devices[: len(ctx.devices)]]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = reduced.window[1] - reduced.window[0]
        line["breakdown"] = xplane.breakdown(reduced)
        shutil.rmtree(log_dir, ignore_errors=True)
    if rehearsal:
        # The arithmetic ran; what it gave on this backend is no device
        # metric and is not printed under a device metric's name.
        line["metrics"] = {k: "computed, not shown: a rehearsal measures no device" for k in metrics}
        line["rehearsal"] = True
    return line
