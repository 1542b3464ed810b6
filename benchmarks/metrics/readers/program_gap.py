"""Median idle gap on the device between two consecutive executions of the
programs matching ``programs`` (a regular expression on the module name in
the trace), in milliseconds, on the first device. Gaps during which the
host was inside one of ``exclude_host_spans`` (waiting for the next request
to fall due) are left out: nothing was resident then."""

from benchmarks.trace import xplane


def read(reading, programs: str, exclude_host_spans=()):
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    runs = xplane.matching(trace.devices[0].modules, programs)
    gaps = xplane.gaps_between(runs)
    if exclude_host_spans:
        skip = [(s, e) for n, s, e in trace.host if n in exclude_host_spans]
        gaps = [g for g in gaps if not any(s < g[1] and e > g[0] for s, e in skip)]
    return xplane.median_ms([b - a for a, b in gaps])
