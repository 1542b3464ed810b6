"""One part of the device's idle gap between two step programs, by the
engine phase the host was in, in milliseconds: the mean over the gaps.

The gaps are those `program_gap` takes: between consecutive executions of
the programs matching ``programs`` on the first device, in the traced
stretch, with the gaps the host spent inside one of ``exclude_host_spans``
left out. Each gap's time is split by the program spans covering it
(`program.spans_of`, on the trace's clock):

- ``fetch``: inside a ``serve_fetch``: the device had finished and the
  host had not yet been answered;
- ``engine``: inside a ``serve_prefill`` / ``serve_decode`` and outside its
  fetch: admission, dispatch, handing out tokens;
- ``caller``: outside every step span: the loop that calls `Engine.step`.

The three parts add up to the mean of the gaps whose median is
`program_gap`'s. ``None`` on a trace without the program's spans.
"""

from benchmarks.stats import union_length
from benchmarks.trace import program, xplane

PARTS = ("fetch", "engine", "caller")


def _covered(gap, intervals) -> float:
    lo, hi = gap
    return union_length([(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi])


def split(reading, programs: str, exclude_host_spans=()) -> list[dict[str, float]] | None:
    """For each gap, its seconds in each part; ``None`` without spans."""
    trace = reading.trace
    if trace is None or not trace.devices:
        return None
    spans = program.spans_of(reading)
    fetches = [(s.start, s.end) for s in spans if s.name == "serve_fetch"]
    steps = [(s.start, s.end) for s in spans if s.name in program.STEP_SPANS] + fetches
    if not steps:
        return None
    gaps = xplane.gaps_between(xplane.matching(trace.devices[0].modules, programs))
    if exclude_host_spans:
        skip = [(s, e) for n, s, e in trace.host if n in exclude_host_spans]
        gaps = [g for g in gaps if not any(s < g[1] and e > g[0] for s, e in skip)]
    out = []
    for gap in gaps:
        fetch, step = _covered(gap, fetches), _covered(gap, steps)
        out.append({"fetch": fetch, "engine": step - fetch, "caller": (gap[1] - gap[0]) - step})
    return out


def read(reading, part: str, programs: str, exclude_host_spans=()):
    if part not in PARTS:
        raise ValueError(f"gap_phase: part {part!r} is not one of {PARTS}")
    parts = split(reading, programs, exclude_host_spans)
    if not parts:
        return None
    return 1e3 * sum(p[part] for p in parts) / len(parts)
