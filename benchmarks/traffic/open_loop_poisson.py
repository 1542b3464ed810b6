"""Open-loop serving traffic: independent users, arrivals on a schedule.

Requests are due at the times of a Poisson process of fixed ``rate`` whatever
the engine does, so a slow engine builds a queue and the wait shows in the
time to first token (timed from when a request was *due*). Parameters (the
cell file's ``traffic``): ``rate`` (requests/s), ``prompt_tokens`` and
``new_tokens`` (length distributions, `lengths.py`), ``warm_seconds`` of the
same traffic before the window opens so that it opens on a busy engine, and
``cool_seconds`` of it after the window closes so that the window's last
requests finish under the same load (neither is measured).

The schedule is a fixed trace, part of the mix: the ``n`` mid-quantiles of
each distribution, in an order drawn from the file's own ``schedule_seed``,
and the window holds exactly ``round(rate * seconds)`` requests. ``--seed``
draws the weights and what the prompts say, never when they arrive or how
long they are: at some 70 requests to a window the order alone moved the
95th percentile of the time to first token by 60% between seeds (PERF.md,
findings of PR 25), so a tail can be judged only on one trace replayed.
"""

from __future__ import annotations

import numpy as np

from . import lengths
from .jobs import Job, Schedule

SYSTEM = "engine"


def _phase(params: dict, rng, phase: str, start: float, seconds: float) -> list[Job]:
    n = max(int(round(params["rate"] * seconds)), 1)
    due = start + np.cumsum(lengths.gaps({"dist": "exponential", "mean": 1.0}, n, seconds, rng))
    prompts = lengths.token_counts(params["prompt_tokens"], n, rng)
    news = lengths.token_counts(params["new_tokens"], n, rng)
    return [
        Job(due=float(t), prompt_tokens=int(p), new_tokens=int(m), phase=phase)
        for t, p, m in zip(due, prompts, news)
    ]


def schedule(params: dict, seconds: float) -> Schedule:
    rng = np.random.default_rng([int(params["schedule_seed"]), 0x6F70])
    warm, cool = params["warm_seconds"], params["cool_seconds"]
    jobs = (
        _phase(params, rng, "warm", -warm, warm)
        + _phase(params, rng, "window", 0.0, seconds)
        + _phase(params, rng, "cool", seconds, cool)
    )
    return Schedule(initial=jobs, warm_seconds=warm, measured_by="due")
