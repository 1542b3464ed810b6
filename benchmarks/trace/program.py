"""What the program says about itself in a profiler trace (ISSUE 27).

`xplane.load` keeps the benchmark's own host spans; this module reads the
same `.xplane.pb` once more for the program's: every `telemetry.span()` is a
`TraceAnnotation`, so a capture started by the benchmark's `Tracer` holds

- ``serve_prefill`` / ``serve_decode``, one for each engine step, and under
  them ``serve_admit``, ``serve_dispatch`` (stats: ``bucket``, ``slot``,
  ``rid`` for a prefill chunk; ``resident``, ``block`` for a decode step),
  ``serve_fetch`` (the host waits for the device) and ``serve_emit``;
- ``train_step`` around each jitted training step;

on the clock of the device's operations. Kernels need no second reading: a
v5e trace names an operation by its HLO text, and a Pallas kernel's name
rides in it as ``frontend_attributes={kernel_metadata={"kernel":"<name>"}}``
(`ops.flash_attention.tuned_call_kwargs`), so `kernel_of` reads it from the
names `xplane.load` already holds.

A program without these spans or names (the parent of ISSUE 27) gives empty
lists here, and every reader built on them returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
from typing import Sequence

from ..stats import union_length
from . import xplane

PROGRAM_SPANS = (
    "serve_prefill",
    "serve_decode",
    "serve_admit",
    "serve_dispatch",
    "serve_fetch",
    "serve_emit",
    "train_step",
)
STEP_SPANS = ("serve_prefill", "serve_decode")
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
_KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel":"([^"]+)"')


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # seconds, the clock of `xplane.Trace`
    end: float
    stats: dict


def trace_file(reading) -> str | None:
    """The `.xplane.pb` of this run's capture: the harness removes it only
    after the readers have run."""
    from .. import harness

    try:
        return xplane.find_xplane(os.path.join(harness.TRACE_DIR, reading.cell["name"]))
    except FileNotFoundError:
        return None


@functools.lru_cache(maxsize=2)
def load_spans(path: str) -> tuple[Span, ...]:
    """The program's host spans in ``path``, in start order (a parent
    before its children)."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    start = e.start_ns * 1e-9
                    stats = {k: v for k, v in e.stats if not k.startswith("_")}
                    spans.append(Span(e.name, start, start + e.duration_ns * 1e-9, stats))
    return tuple(sorted(spans, key=lambda s: (s.start, -s.end)))


def spans_of(reading) -> tuple[Span, ...]:
    """The program's spans inside the traced stretch; empty without a
    device trace (a rehearsal, an untraced run)."""
    trace = reading.trace
    if trace is None or not trace.devices:
        return ()
    path = trace_file(reading)
    if path is None:
        return ()
    lo, hi = trace.window
    return tuple(s for s in load_spans(path) if s.start >= lo and s.end <= hi)


def children(parent: Span, spans: Sequence[Span], name: str) -> list[Span]:
    return [s for s in spans if s.name == name and s.start >= parent.start and s.end <= parent.end]


# -------------------------------------------------------------------- kernels
def kernel_of(op_name: str) -> str | None:
    """The name a Pallas kernel's operation carries, ``""`` for a kernel
    without one, ``None`` for any other operation."""
    if KERNEL_CALL not in op_name or " custom-call(" not in op_name:
        return None
    found = _KERNEL.search(op_name)
    return found.group(1) if found else ""


def kernel_events(device: xplane.Device, kernels: str | None) -> list[tuple[float, float]]:
    """(start, end) of the kernel operations whose name matches the regular
    expression ``kernels`` (``None``: every kernel, named or not)."""
    rx = re.compile(kernels) if kernels is not None else None
    out = []
    for name, s, e in device.ops:
        kernel = kernel_of(name)
        if kernel is not None and (rx is None or rx.search(kernel)):
            out.append((s, e))
    return out


def executions(trace: xplane.Trace, programs: str) -> list[xplane.Event]:
    """Whole executions of the programs matching ``programs`` on the first
    device, in start order."""
    runs = xplane.whole(xplane.matching(trace.devices[0].modules, programs), trace.window)
    return sorted(runs, key=lambda ev: ev[1])


def kernel_seconds(trace: xplane.Trace, runs: Sequence[xplane.Event], kernels: str | None) -> list[float]:
    """For each execution in ``runs``, the seconds in which a kernel
    matching ``kernels`` ran."""
    events = kernel_events(trace.devices[0], kernels)
    return [
        union_length([(max(s, lo), min(e, hi)) for s, e in events if e > lo and s < hi])
        for _, lo, hi in runs
    ]


# ------------------------------------------------------ spans and executions
def pair_dispatches(
    dispatches: Sequence[Span], runs: Sequence[xplane.Event], fetches: Sequence[Span]
) -> list[tuple[Span, xplane.Event]]:
    """``dispatches`` (spans around single jitted calls of one program) and
    that program's executions on the device, paired in dispatch order. The
    device runs what it is given in order, but a capture does not say how
    many calls were still pending on it when it began; it does say when the
    host last waited for the device: at the end of a ``serve_fetch`` every
    earlier call has run. So the pairing starts at the first fetch in hand:
    the dispatches that begin after it and the executions that begin after
    it are the same calls, one for one. A dispatch whose execution fell
    after the capture's end stays unpaired."""
    if not fetches:
        return []
    anchor = min(f.end for f in fetches)
    later = [d for d in dispatches if d.start >= anchor]
    ran = [r for r in sorted(runs, key=lambda ev: ev[1]) if r[1] >= anchor]
    return list(zip(later, ran))


def executions_of_bucket(reading, programs: str, bucket: int) -> list[xplane.Event]:
    """Whole executions of the prefill programs matching ``programs`` that a
    ``serve_dispatch`` span with ``bucket`` dispatched, on the first device."""
    trace = reading.trace
    spans = spans_of(reading)
    pairs = pair_dispatches(
        [s for s in spans if s.name == "serve_dispatch" and "bucket" in s.stats],
        xplane.matching(trace.devices[0].modules, programs),
        [s for s in spans if s.name == "serve_fetch"],
    )
    whole = set(xplane.whole([run for _, run in pairs], trace.window))
    return [run for span, run in pairs if span.stats["bucket"] == bucket and run in whole]
