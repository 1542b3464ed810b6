"""Median host time of one engine step that the device cannot overlap, in
milliseconds on the trace's clock: each ``serve_prefill`` / ``serve_decode``
span of the traced stretch minus its ``serve_fetch`` children (in a fetch
the host waits for the device; in the rest - admission, array building,
dispatch, handing out tokens - the device waits for the host or runs ahead
of it). ``None`` where the trace holds no such span."""

from benchmarks.stats import median
from benchmarks.trace import program


def read(reading):
    spans = program.spans_of(reading)
    steps = [s for s in spans if s.name in program.STEP_SPANS]
    if not steps:
        return None
    return 1e3 * median([
        (p.end - p.start) - sum(f.end - f.start for f in program.children(p, spans, "serve_fetch"))
        for p in steps
    ])
