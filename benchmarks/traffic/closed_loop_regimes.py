"""Closed-loop serving traffic in several length regimes: ``clients`` callers
that each wait for a reply and then send their next request, as
`closed_loop.py`, but each request is of one of the cell's ``regimes`` (short
chat turns beside long-document questions on one queue).

Parameters (the cell file's ``traffic``): ``clients``,
``requests_per_client``, ``warm_seconds``, ``drain_seconds``,
``schedule_seed`` as there, and ``regimes``: a list of ``{"name", "share",
"prompt_tokens", "new_tokens"}`` whose shares sum to 1. The requests are one
fixed set: regime r gives ``round(share_r * n)`` of the ``n`` requests (the
last regime the remainder), their lengths the mid-quantiles of its two
distributions (`lengths.py`), each paired in an order drawn from
``schedule_seed``; the whole set is then dealt to the clients in an order
drawn from the same seed. ``--seed`` draws the weights and what the prompts
say, never how long they are or who sends which.
"""

from __future__ import annotations

import numpy as np

from . import lengths
from .jobs import Job, Schedule

SYSTEM = "engine_smallthinker"


def request_lengths(params: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(prompt tokens, new tokens, regime index) of the fixed set, dealt."""
    rng = np.random.default_rng([int(params["schedule_seed"]), 0x7267])
    n = params["clients"] * params["requests_per_client"]
    regimes = params["regimes"]
    counts = [int(round(r["share"] * n)) for r in regimes[:-1]]
    counts.append(n - sum(counts))
    prompts = np.concatenate(
        [lengths.token_counts(r["prompt_tokens"], c, rng) for r, c in zip(regimes, counts)]
    )
    news = np.concatenate(
        [lengths.token_counts(r["new_tokens"], c, rng) for r, c in zip(regimes, counts)]
    )
    which = np.repeat(np.arange(len(regimes)), counts)
    order = rng.permutation(n)
    return prompts[order], news[order], which[order]


def schedule(params: dict, seconds: float) -> Schedule:
    clients, each = params["clients"], params["requests_per_client"]
    prompts, news, _ = request_lengths(params)
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
