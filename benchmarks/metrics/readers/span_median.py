"""Median length, in milliseconds, of the benchmark's own host span
``span`` over the measured window (host clock)."""

from benchmarks.stats import median


def read(reading, span: str):
    o = reading.outcome
    values = reading.spans.durations_ms(span, since=o["t_open"], until=o["t_close"])
    return median(values) if values else None
