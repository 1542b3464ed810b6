"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

`jax.profiler.ProfileData` reads the file with nothing but JAX. What a v5e
trace holds today (looked at by hand, PR 25; see PERF.md "Layers"):

- one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one
  event per execution of a jitted program, named ``jit_<function>(<id>)``)
  and a line ``XLA Ops`` (one event per HLO operation or fusion inside it);
- one plane ``/host:CPU`` whose lines are threads; the benchmark's
  `TraceAnnotation` spans are events on the thread that made them.

All planes share one clock (nanoseconds from the start of the trace).
Everything here is interval arithmetic on ``(name, start, end)`` tuples, so
it can be checked on a small recorded trace (`benchmarks/tests`).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Sequence

from ..spans import HOST_SPANS, WINDOW_SPAN
from ..stats import median, union_length

Event = tuple[str, float, float]  # name, start_s, end_s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# by an operation's kind (`describe`), so its -start and -done halves count too
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast)"
)


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(describe(name)[0]))


@dataclasses.dataclass
class Device:
    index: int
    modules: list[Event]
    ops: list[Event]
    # asynchronous operations from their start to their done (copies and,
    # across chips, collectives): they run beside the operations of `ops`
    async_ops: list[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[Event]  # the benchmark's own spans, by name
    window: tuple[float, float]  # the traced stretch: the `bench-window` span


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line) -> list[Event]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def clip(events: Iterable[Event], window: tuple[float, float]) -> list[Event]:
    """The parts of ``events`` inside ``window``."""
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            devices.append(
                Device(
                    index=int(match.group(1)),
                    modules=_events(lines[MODULE_LINE]) if MODULE_LINE in lines else [],
                    ops=_events(lines[OP_LINE]) if OP_LINE in lines else [],
                    async_ops=_events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else [],
                )
            )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line) if ev[0] in wanted)
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:  # no marker: from the first device operation to the last
        ends = [t for d in devices for _, s, e in d.ops for t in (s, e)]
        window = (min(ends), max(ends)) if ends else (0.0, 0.0)
    devices.sort(key=lambda d: d.index)
    host = sorted((ev for ev in host if ev[0] != WINDOW_SPAN), key=lambda ev: ev[1])
    for d in devices:
        d.modules = clip(d.modules, window)
        d.ops = clip(d.ops, window)
        d.async_ops = clip(d.async_ops, window)
    return Trace(devices=devices, host=clip(host, window), window=window)


# ------------------------------------------------------------------ reductions
def busy_seconds(device: Device) -> float:
    """Seconds in which at least one operation ran on the device."""
    return union_length([(s, e) for _, s, e in device.ops])


def matching(events: Sequence[Event], pattern: str) -> list[Event]:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def whole(events: Sequence[Event], window: tuple[float, float]) -> list[Event]:
    """Events that neither start at the window's opening nor end at its
    close: clipping cut those, and their length says nothing."""
    lo, hi = window
    return [ev for ev in events if ev[1] > lo and ev[2] < hi]


def busy_inside(device: Device, span: tuple[float, float]) -> float:
    return union_length([(max(s, span[0]), min(e, span[1])) for _, s, e in device.ops if e > span[0] and s < span[1]])


def gaps_between(events: Sequence[Event]) -> list[tuple[float, float]]:
    """The idle stretches between consecutive events of one line."""
    ordered = sorted(events, key=lambda ev: ev[1])
    return [(a[2], b[1]) for a, b in zip(ordered, ordered[1:]) if b[1] > a[2]]


def idle_gaps(device: Device, window: tuple[float, float]) -> list[tuple[float, float]]:
    """Every stretch of the window in which no operation ran."""
    out, reach = [], window[0]
    for _, s, e in sorted(device.ops, key=lambda ev: ev[1]):
        if s > reach:
            out.append((reach, s))
        reach = max(reach, e)
    if window[1] > reach:
        out.append((reach, window[1]))
    return out


def attribute(gap: tuple[float, float], host: Sequence[Event]) -> str:
    """The host span that covers most of ``gap`` (innermost on a tie of
    cover, since inner spans are shorter), or ``"(no span)"``."""
    best, best_cover, best_len = "(no span)", 0.0, float("inf")
    for name, s, e in host:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        length = e - s
        if cover > best_cover * 1.0001 or (abs(cover - best_cover) <= best_cover * 1e-4 and length < best_len):
            best, best_cover, best_len = name, cover, length
    return best


def exposed_collective_seconds(device: Device) -> float:
    """Seconds in which only collective operations ran on the device: the
    union of collective intervals (a synchronous one on the operations'
    line, an asynchronous one from its start to its done on the async line,
    and the -done operation that waits for it) minus its overlap with the
    union of all other operations."""
    coll, rest = [], []
    for name, s, e in device.ops:
        kind = describe(name)[0]
        if kind in CONTAINERS:
            continue  # a loop holds both kinds: its children say which
        (coll if COLLECTIVE.match(kind) else rest).append((s, e))
    coll += [(s, e) for name, s, e in device.async_ops if is_collective(name)]
    return union_length(coll + rest) - union_length(rest)


_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
CONTAINERS = ("while", "conditional", "call")


def describe(name: str, width: int = 96) -> tuple[str, str]:
    """(kind, short label) of an operation. A v5e trace names an operation
    by its whole HLO text, ``%name = shape kind(operands), attributes``; the
    label is that text without layouts, cut to ``width``, and with the
    custom-call target kept when there is one."""
    text = _LAYOUT.sub("", name)
    head, _, rest = text.partition(" = ")
    if not rest:
        return "", text[:width]
    if rest.startswith("("):  # a tuple shape: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        after = rest[i + 1 :].lstrip()
    else:
        after = rest.partition(" ")[2]
    kind = after.partition("(")[0].strip()
    target = re.search(r'custom_call_target="([^"]+)"', name)
    label = (head.lstrip("%") + " = " + rest)[:width]
    if target:
        label += f" [{target.group(1)}]"
    return kind, label


def breakdown(trace: Trace, top: int = 10) -> dict[str, list]:
    """The device operations that took most time (operations that only
    contain others - loops, conditionals, calls - left out) and the idle
    time by what the host was doing, on the device that was idle longest."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    device = min(trace.devices, key=busy_seconds)
    by_name: dict[str, float] = {}
    for name, s, e in device.ops:
        kind, label = describe(name)
        if kind in CONTAINERS:
            continue
        by_name[label] = by_name.get(label, 0.0) + (e - s)
    by_span: dict[str, float] = {}
    for gap in idle_gaps(device, trace.window):
        label = attribute(gap, trace.host)
        by_span[label] = by_span.get(label, 0.0) + (gap[1] - gap[0])
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_name), "idle_gaps": rank(by_span)}


def median_ms(values: Sequence[float]) -> float | None:
    return median(values) * 1e3 if values else None
