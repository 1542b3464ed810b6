"""The ``q``-th percentile of the window's ``samples[of]`` (all of them: a
tail is the tail of every measured request)."""

from benchmarks.stats import percentile


def read(reading, of: str, q: float):
    values = reading.outcome["samples"].get(of)
    return percentile(values, q) if values else None
