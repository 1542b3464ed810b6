"""What a serving traffic generator hands the driver."""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np


def prompt_tokens(seed: int, index: int, n: int, vocab_size: int) -> np.ndarray:
    """What the ``index``-th request of a run says: ``n`` tokens drawn from
    ``--seed``, so that every prompt is unique and no two seeds share one."""
    rng = np.random.default_rng([int(seed), 0x7470, int(index)])
    return rng.integers(0, vocab_size, n, dtype=np.int32)


@dataclasses.dataclass
class Job:
    """One request to send. ``due`` is in seconds from the opening of the
    measured window (negative before it)."""

    due: float
    prompt_tokens: int
    new_tokens: int
    phase: str
    client: int = -1


@dataclasses.dataclass
class Schedule:
    """``initial`` jobs are known up front. ``after(job, now)`` is asked
    when a job completes and may return that client's next job (closed
    loops). ``measured_by`` says which jobs the window's metrics count:
    those ``"due"`` inside it (open loop) or those whose ``"completion"``
    fell inside it (closed loop)."""

    initial: list[Job]
    warm_seconds: float
    measured_by: str
    after: Optional[Callable[[Job, float], Optional[Job]]] = None
