"""Traffic generators: the same schedule for the same `schedule_seed`,
another order for another, the same set of work in both; and inputs that
follow `--seed`."""

import numpy as np
import pytest

from benchmarks.traffic import closed_loop, jobs, lengths, open_loop_poisson, train_stream

CHAT = {
    "rate": 8.0, "schedule_seed": 7,
    "prompt_tokens": {"dist": "lognormal", "median": 200, "sigma": 0.7, "min": 32, "max": 768},
    "new_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.5, "min": 16, "max": 256},
    "warm_seconds": 5.0, "cool_seconds": 10.0, "drain_seconds": 20.0,
}
LONG = {
    "clients": 8, "requests_per_client": 16, "schedule_seed": 3,
    "prompt_tokens": {"dist": "loguniform", "min": 2048, "max": 6144},
    "new_tokens": {"dist": "uniform", "min": 32, "max": 96},
    "warm_seconds": 6.0, "drain_seconds": 30.0,
}


def _rows(schedule):
    return [(j.due, j.prompt_tokens, j.new_tokens, j.phase) for j in schedule.initial]


def test_open_loop_same_seed_same_schedule_other_seed_other_order():
    a = _rows(open_loop_poisson.schedule(CHAT, 30.0))
    b = _rows(open_loop_poisson.schedule(CHAT, 30.0))
    c = _rows(open_loop_poisson.schedule(dict(CHAT, schedule_seed=8), 30.0))
    assert a == b
    assert a != c
    window = lambda rows: [r for r in rows if r[3] == "window"]
    # the same work in every seed: same count, same sets of lengths and gaps
    assert len(window(a)) == len(window(c)) == 240
    assert sorted(r[1] for r in window(a)) == sorted(r[1] for r in window(c))
    assert sorted(r[2] for r in window(a)) == sorted(r[2] for r in window(c))
    gaps = lambda rows: np.sort(np.diff([0.0] + [r[0] for r in window(rows)]))
    np.testing.assert_allclose(gaps(a), gaps(c), atol=1e-9)
    # the window's arrivals end exactly at its close, and every phase is where it says
    assert window(a)[-1][0] == pytest.approx(30.0)
    assert all(r[0] <= 1e-9 for r in a if r[3] == "warm")
    assert all(r[0] > 30.0 - 1e-9 for r in a if r[3] == "cool")


def test_prompts_follow_the_run_seed_and_take_one_beyond_32_bits():
    big = 2**31 + 12345
    a, b = jobs.prompt_tokens(big, 3, 50, 1000), jobs.prompt_tokens(big, 3, 50, 1000)
    assert np.array_equal(a, b) and a.dtype == np.int32 and a.max() < 1000
    assert not np.array_equal(a, jobs.prompt_tokens(big + 1, 3, 50, 1000))  # another seed
    assert not np.array_equal(a, jobs.prompt_tokens(big, 4, 50, 1000))  # another request


def test_lengths_are_clipped_and_centred():
    n = lengths.token_counts(CHAT["prompt_tokens"], 1000, np.random.default_rng(0))
    assert n.min() >= 32 and n.max() <= 768
    assert 190 <= np.median(n) <= 210
    u = lengths.token_counts(LONG["prompt_tokens"], 1000, np.random.default_rng(0))
    assert u.min() >= 2048 and u.max() <= 6144
    assert 3400 <= np.exp(np.mean(np.log(u))) <= 3700  # log-uniform: geometric mean 3547


def test_closed_loop_deals_the_same_set_and_sends_on_completion():
    a = closed_loop.schedule(LONG, 30.0)
    b = closed_loop.schedule(dict(LONG, schedule_seed=4), 30.0)
    assert len(a.initial) == 8 and {j.client for j in a.initial} == set(range(8))
    assert _rows(a) == _rows(closed_loop.schedule(LONG, 30.0))
    assert _rows(a) != _rows(b)
    nxt = a.after(a.initial[2], 1.5)
    assert nxt.client == 2 and nxt.due == 1.5
    assert a.after(a.initial[2], 30.0) is None  # the window has closed: no new request

    def everything(s):
        out = [(j.prompt_tokens, j.new_tokens) for j in s.initial]
        for j in s.initial:
            while (n := s.after(j, 0.0)) is not None:
                out.append((n.prompt_tokens, n.new_tokens))
        return out

    assert sorted(p for p, _ in everything(closed_loop.schedule(LONG, 30.0))) == sorted(
        p for p, _ in everything(closed_loop.schedule(dict(LONG, schedule_seed=4), 30.0))
    )


def test_train_stream_is_seeded():
    p = {"batch_size": 2, "seq_len": 16, "sequences": 8}
    a, b, c = (train_stream.token_stream(p, s, 100) for s in (1, 1, 2))
    assert np.array_equal(a["sequences"], b["sequences"]) and np.array_equal(a["probe"], b["probe"])
    assert not np.array_equal(a["sequences"], c["sequences"])
    assert a["probe"].shape == (2, 16) and a["sequences"].max() < 100
