"""Closed-loop serving traffic: ``clients`` callers that each wait for a
reply and then send their next request (document pipelines, batch workers).

A slow engine receives less load, the backlog is bounded by the number of
clients, and with more clients than slots the engine is kept above its knee
without a rate having to be found. Parameters (the cell file's ``traffic``):
``clients``, ``prompt_tokens`` and ``new_tokens`` (length distributions,
`lengths.py`), ``warm_seconds`` of the same loop before the window opens,
``requests_per_client`` (how many each client has ready: more than it can
finish). Measured: the tokens streamed inside the window.

The lengths are a fixed set (the mid-quantiles of each distribution) dealt
to the clients in an order drawn from the file's own ``schedule_seed``;
``--seed`` draws the weights and what the prompts say (see
`open_loop_poisson.py` for why).
"""

from __future__ import annotations

import numpy as np

from . import lengths
from .jobs import Job, Schedule

SYSTEM = "engine"


def schedule(params: dict, seconds: float) -> Schedule:
    rng = np.random.default_rng([int(params["schedule_seed"]), 0x636C])
    clients, each = params["clients"], params["requests_per_client"]
    n = clients * each
    prompts = lengths.token_counts(params["prompt_tokens"], n, rng)
    news = lengths.token_counts(params["new_tokens"], n, rng)
    warm = params["warm_seconds"]
    queues = [
        [
            Job(due=-warm, prompt_tokens=int(prompts[c * each + i]), new_tokens=int(news[c * each + i]),
                phase="loop", client=c)
            for i in range(each)
        ]
        for c in range(clients)
    ]
    initial = [q.pop(0) for q in queues]

    def after(job: Job, now: float):
        queue = queues[job.client]
        if not queue or now >= seconds:
            return None
        nxt = queue.pop(0)
        nxt.due = now
        return nxt

    return Schedule(initial=initial, warm_seconds=warm, measured_by="completion", after=after)
