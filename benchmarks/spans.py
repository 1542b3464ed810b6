"""The benchmark's own host spans, around its calls into each layer.

A span is ``(name, start, end)`` on `time.perf_counter`. While a profiler
trace is live each span is also a `jax.profiler.TraceAnnotation`, which
puts it on the trace's clock beside the device's operations, so that an idle
gap on the device can be given to what the host was doing in it. Spans stay
in memory; nothing is written while the window runs.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator


class Spans:
    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False  # set while a profiler trace is live

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        if self.annotate:
            import jax

            marker = jax.profiler.TraceAnnotation(name)
        else:
            marker = contextlib.nullcontext()
        with marker:
            start = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, start, time.perf_counter()))

    def durations_ms(self, name: str, since: float = 0.0, until: float = float("inf")) -> list[float]:
        return [
            (end - start) * 1e3
            for n, start, end in self.records
            if n == name and start >= since and end <= until
        ]


class Heartbeat:
    """A thread that sleeps ``period`` seconds at a time and keeps every
    tick that came more than ``late`` seconds late. When a step stalls, a
    late tick says that the whole process (or its machine) stood still;
    ticks on time say that the host was free and waited for the device."""

    def __init__(self, period: float = 0.01, late: float = 0.1) -> None:
        self.period, self.late = period, late
        self.late_ticks: list[tuple[float, float]] = []  # (slept from, woke at)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.is_set():
            start = time.perf_counter()
            time.sleep(self.period)
            woke = time.perf_counter()
            if woke - start > self.late:
                self.late_ticks.append((start, woke))

    def start(self) -> "Heartbeat":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def longest_ms(self, since: float, until: float) -> float:
        """The longest late tick that overlaps ``since``..``until``."""
        inside = [b - a for a, b in self.late_ticks if b > since and a < until]
        return max(inside, default=0.0) * 1e3


# The names the gap attribution knows (ISSUE 25); a span with another name
# is recorded and simply not used for attribution.
HOST_SPANS = (
    "next-batch",
    "step-dispatch",
    "block",
    "submit",
    "engine-step",
    "client-callback",
    "sleep-until-due",
)
WINDOW_SPAN = "bench-window"
