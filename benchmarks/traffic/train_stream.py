"""Training traffic: fresh sequences of random tokens from the seed.

Parameters (the cell file's ``traffic``): ``batch_size`` (global),
``seq_len``, ``sequences`` (how many distinct sequences the loader cycles
through, reshuffled each epoch). The probe batch, on which the reference is
compared, is drawn apart from the stream.
"""

from __future__ import annotations

import numpy as np

SYSTEM = "trainer"


def token_stream(params: dict, seed: int, vocab_size: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng([int(seed), 0x7261])
    shape = (params["sequences"], params["seq_len"])
    probe = (params["batch_size"], params["seq_len"])
    return {
        "sequences": rng.integers(0, vocab_size, shape, dtype=np.int32),
        "probe": rng.integers(0, vocab_size, probe, dtype=np.int32),
    }
