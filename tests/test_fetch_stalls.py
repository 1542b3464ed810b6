"""The engine's stall rule (`serving/fetch_stalls.py`) as a pure unit: waits
are handed in as numbers, so no clock runs. The engine side (a slow fetch
counted, its record joined to its span) is in `tests/test_trace.py`."""

import pytest

from accelerate_tpu.serving.fetch_stalls import FetchStalls, fetch_key


def _rule_with(key, waits, rule=None):
    rule = rule or FetchStalls()
    for w in waits:
        assert rule.judge(key, w) is None
    return rule


def test_nothing_is_flagged_before_eight_waits_of_a_key():
    rule = FetchStalls()
    assert [rule.judge("decode", w) for w in [0.01] * 7 + [5.0]] == [None] * 8
    # the ninth wait is the first judged: against the median of the eight
    assert rule.judge("decode", 5.0) == pytest.approx(0.01)


@pytest.mark.parametrize("factor, flagged", [(1.6, True), (1.4, False), (1.5, False)])
def test_a_wait_is_flagged_over_one_and_a_half_times_the_median(factor, flagged):
    rule = _rule_with("decode", [0.010] * 8)
    usual = rule.judge("decode", 0.010 * factor)
    assert (usual == pytest.approx(0.010)) if flagged else usual is None


def test_the_median_is_of_the_last_64_waits():
    rule = _rule_with("decode", [1.0] * 64)
    for _ in range(64):  # a slower machine: the rule follows it, stall by stall
        rule.judge("decode", 0.1)
    assert rule.judge("decode", 0.14) is None
    assert rule.judge("decode", 0.16) == pytest.approx(0.1)


def test_keys_are_kept_apart():
    rule = _rule_with("1024+decode", [0.080] * 8)
    # a decode step alone has no history yet: never judged by the chunk's waits
    assert [rule.judge("decode", 0.015) for _ in range(8)] == [None] * 8
    assert rule.judge("decode", 0.030) == pytest.approx(0.015)
    assert rule.judge("1024+decode", 0.100) is None  # 1.25 x its own usual
    assert rule.judge("1024+decode", 0.130) == pytest.approx(0.080)


def test_the_excess_summed_over_stalls():
    rule = _rule_with("decode", [0.010] * 8)
    excess = 0.0
    for wait in (0.016, 0.030, 0.014, 0.012, 0.100):
        usual = rule.judge("decode", wait)
        if usual is not None:
            excess += wait - usual
    # 0.016, 0.030 and 0.100 stalled against a median that stays 0.010
    assert excess == pytest.approx(0.006 + 0.020 + 0.090)


@pytest.mark.parametrize(
    "tally, key",
    [
        ({"decode": 1}, "decode"),
        ({1024: 1, "decode": 1}, "1024+decode"),
        ({1024: 1}, "1024"),
        ({"decode": 3}, "3xdecode"),
        ({"copy": 2, 1024: 1, 256: 2}, "2x256+1024+2xcopy"),
    ],
)
def test_the_key_names_the_work_waited_on(tally, key):
    assert fetch_key(tally) == key
