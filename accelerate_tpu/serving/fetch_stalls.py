"""What an engine fetch waited for, and whether it stalled.

The engine's host waits for the device at one place: the fetch of a step's
tokens (`Engine._fetch`). What it waits for is every program dispatched
since the fetch before it: prefill chunks by bucket, decode steps, prefix
copies. That work is the key the stall rule compares a wait under (`fetch_key`
writes it out for a reader): a fetch that waited on a 1024-row chunk and a decode
step is judged against earlier fetches that waited on the same, never
against a decode step alone. So a stalled fetch is the same work waited on
longer, and more work queued ahead shows as another key, not as a stall.
"""

from __future__ import annotations

from bisect import bisect_left, insort
from collections import deque

__all__ = ["FetchStalls", "fetch_key"]


def fetch_key(tally: dict) -> str:
    """The work a fetch waits on, from a tally of the programs dispatched
    since the last fetch (a bucket's rows for a prefill chunk, ``"copy"``
    for a prefix copy, ``"decode"`` for a decode step, each with its count):
    ``"decode"``, ``"1024+decode"``, ``"2x256+copy"``, ``"3xdecode"``."""
    buckets = sorted(p for p in tally if isinstance(p, int))
    order = buckets + [p for p in ("copy", "decode") if p in tally]
    return "+".join(str(p) if tally[p] == 1 else f"{tally[p]}x{p}" for p in order)


class FetchStalls:
    """The stall rule, kept per key (any hashable that names the work a
    fetch waited on): once a key has ``warmup`` waits, a wait longer than
    ``factor`` times the median of the key's last ``window`` waits (those
    before it) is a stalled fetch. Every wait, a stalled one too, joins its
    key's history. The state is bounded: per key seen, its last ``window``
    waits in arrival order and the same kept sorted, so the median is read
    by index at every fetch."""

    warmup = 8
    window = 64
    factor = 1.5

    def __init__(self) -> None:
        self._waits: dict = {}  # key -> (deque in arrival order, sorted list)

    def judge(self, key, wait: float) -> float | None:
        """Add one wait of ``key``. Returns the key's usual wait (the median
        it was judged against) when this one stalled, else ``None``."""
        history = self._waits.get(key)
        if history is None:
            history = self._waits[key] = (deque(), [])
        arrived, ordered = history
        usual = None
        n = len(ordered)
        if n >= self.warmup:
            half = n // 2
            median = ordered[half] if n % 2 else (ordered[half - 1] + ordered[half]) / 2
            if wait > self.factor * median:
                usual = median
        if n == self.window:
            del ordered[bisect_left(ordered, arrived.popleft())]
        arrived.append(wait)
        insort(ordered, wait)
        return usual
