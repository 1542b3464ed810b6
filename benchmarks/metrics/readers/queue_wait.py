"""Mean time, in milliseconds, from a request's submission to its first
prefill chunk (`prefill_started_at - submitted_at` of the engine's
``request`` records in the process's flight recorder: exact, one a
completion), over the requests submitted in the measured window that
started prefill before the profiler did. Per-layer metrics are read in the
traced run, and the loop stands still while the profiler starts and stops:
what queued behind that is the profiler's doing, not the engine's
(PERF.md 7). ``None`` where the program writes no such record."""

from accelerate_tpu.telemetry import flight


def read(reading):
    o = reading.outcome
    seconds = o["t_close"] - o["t_open"]
    profiler_starts = o["t_open"] + reading.cell["trace"]["start_share"] * seconds
    waits = [
        (r["attrs"]["prefill_started_at"] - r["t0"]) * 1e3
        for r in flight.recorder().last()
        if r["name"] == "request"
        and r["t0"] >= o["t_open"]
        and 0.0 < r["attrs"]["prefill_started_at"] < profiler_starts
    ]
    return sum(waits) / len(waits) if waits else None
