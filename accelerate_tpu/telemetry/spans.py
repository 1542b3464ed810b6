"""Span tracer: wall-clock host spans as Chrome-trace JSONL + XPlane bridge.

``span("name")`` times a host-side block. When a span log is open
(:func:`start_trace_log`, or ``ATX_TRACE_DIR`` at first use) each span is
appended to ``spans_<proc>.jsonl`` as one Chrome-trace complete event
(``"ph": "X"``, microsecond ``ts``/``dur``) per line — load with
:func:`chrome_trace` (wraps the lines into the JSON array Perfetto /
chrome://tracing expect). Nesting is tracked with a ``contextvars`` stack so
events carry their parent span and spans in worker threads don't corrupt
each other.

Every span also enters a ``jax.profiler.TraceAnnotation`` (its keyword
attributes become the event's stats), so the same names line up against the
device timeline of any capture of this process, whoever started it: the
program's `profile()`, a bare ``jax.profiler.start_trace``, a profiler
server's capture button. ``step_span`` adds a ``StepTraceAnnotation`` so
step-time views group ops by step number.

Hot-path safety: with no span log open ``span()`` *is* the annotation, and an
annotation with no capture live is a check of one flag — no timestamps, no
I/O.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import json
import os
import threading
import time
from typing import Any, Iterator

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = [
    "span",
    "step_span",
    "start_trace_log",
    "stop_trace_log",
    "trace_log_path",
    "chrome_trace",
]

_SPAN_STACK: contextvars.ContextVar[tuple[str, ...]] = contextvars.ContextVar(
    "atx_span_stack", default=()
)

_writer_lock = threading.Lock()
_writer: "_JsonlWriter | None" = None
_env_checked = False


class _JsonlWriter:
    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._lock = threading.Lock()

    def write(self, event: dict[str, Any]) -> None:
        line = json.dumps(event, separators=(",", ":"), default=str)
        with self._lock:
            if not self._f.closed:
                self._f.write(line + "\n")

    def close(self) -> None:
        # Flush + fsync before closing: the atexit/SystemExit path (exit-75
        # preemption) must leave every event durably on disk, not in a
        # page-cache line a subsequent kill can truncate.
        with self._lock:
            if self._f.closed:
                return
            try:
                self._f.flush()
                os.fsync(self._f.fileno())
            except OSError:
                pass
            self._f.close()


def _process_index() -> int:
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


_atexit_registered = False


def _close_writer_at_exit() -> None:
    # Runs on interpreter shutdown, including ``SystemExit`` paths (exit-75
    # preemption, a drain's sys.exit) and uncaught exceptions — the cases
    # that used to truncate the last events. ``os._exit`` paths (kill-137,
    # the watchdog's default abort) bypass atexit by design; the watchdog
    # dumps its postmortem bundle explicitly before aborting instead.
    writer = _writer
    if writer is not None:
        writer.close()


def start_trace_log(path: str | None = None) -> str:
    """Open the span JSONL log. Default path:
    ``$ATX_TRACE_DIR/spans_<proc>.jsonl``."""
    global _writer, _env_checked, _atexit_registered
    with _writer_lock:
        if _writer is not None:
            return _writer.path
        if path is None:
            base = os.environ.get("ATX_TRACE_DIR", "atx_trace")
            path = os.path.join(base, f"spans_{_process_index()}.jsonl")
        _writer = _JsonlWriter(path)
        _env_checked = True
        if not _atexit_registered:
            atexit.register(_close_writer_at_exit)
            _atexit_registered = True
        return path


def stop_trace_log() -> None:
    global _writer, _env_checked
    with _writer_lock:
        if _writer is not None:
            _writer.close()
            _writer = None
        _env_checked = True


def trace_log_path() -> str | None:
    writer = _writer
    return writer.path if writer is not None else None


def _maybe_open_from_env() -> "_JsonlWriter | None":
    # ATX_TRACE_DIR opt-in checked once, on the first span after import.
    global _env_checked
    if _env_checked:
        return _writer
    with _writer_lock:
        _env_checked = True
    if os.environ.get("ATX_TRACE_DIR"):
        start_trace_log()
    return _writer


def span(name: str, **attrs: Any):
    """Context manager timing a host-side block. With no span log open it is
    the bare `TraceAnnotation`: one flag check while nobody captures."""
    writer = _writer if _env_checked else _maybe_open_from_env()
    if writer is None:
        return TraceAnnotation(name, **attrs)
    return _logged_span(writer, name, attrs)


@contextlib.contextmanager
def _logged_span(writer: _JsonlWriter, name: str, attrs: dict[str, Any]) -> Iterator[None]:
    stack = _SPAN_STACK.get()
    token = _SPAN_STACK.set(stack + (name,))
    start = time.perf_counter()
    wall_us = time.time() * 1e6
    try:
        with TraceAnnotation(name, **attrs):
            yield
    finally:
        dur_us = (time.perf_counter() - start) * 1e6
        _SPAN_STACK.reset(token)
        event: dict[str, Any] = {
            "name": name,
            "ph": "X",
            "ts": wall_us,
            "dur": dur_us,
            "pid": _process_index(),
            "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        args = dict(attrs)
        if stack:
            args["parent"] = stack[-1]
        if args:
            event["args"] = args
        writer.write(event)


@contextlib.contextmanager
def step_span(step: int, name: str = "train") -> Iterator[None]:
    """Span for one training step, under a ``StepTraceAnnotation`` so a
    capture's step-time views number the steps."""
    with StepTraceAnnotation(name, step_num=int(step)):
        with span(f"{name}_step", step=int(step)):
            yield


def mirror_flight_event(
    entry: dict[str, Any], t0_perf: float, t0_wall: float
) -> None:
    """Write a flight-recorder span record (`telemetry/flight.py`) into the
    Chrome-trace JSONL log when one is open, mapping its monotonic
    perf_counter times onto the wall clock via the recorder's anchors, so a
    live ``ATX_TRACE_DIR`` carries the request-scoped spans alongside the
    block spans and `atx trace` can read either surface."""
    writer = _writer if _env_checked else _maybe_open_from_env()
    if writer is None:
        return
    args: dict[str, Any] = {"rid": entry.get("rid", -1)}
    args.update(entry.get("attrs", ()))
    writer.write(
        {
            "name": entry["name"],
            "ph": "X",
            "ts": (t0_wall + (entry["t0"] - t0_perf)) * 1e6,
            "dur": max(0.0, entry["t1"] - entry["t0"]) * 1e6,
            "pid": _process_index(),
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": args,
        }
    )


def chrome_trace(jsonl_path: str) -> dict[str, Any]:
    """Load a span JSONL file as a Chrome-trace/Perfetto ``traceEvents``
    object (``json.dump`` the result to get a loadable ``.json`` trace)."""
    events = []
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
