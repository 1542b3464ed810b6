"""Percentile and spread arithmetic, against values worked by hand."""

import statistics

import pytest

from benchmarks import stats


def test_percentile_interpolates_between_order_statistics():
    values = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(values, 0) == 10.0
    assert stats.percentile(values, 50) == 30.0
    assert stats.percentile(values, 100) == 50.0
    # rank = 4 * 0.95 = 3.8 -> 40 + 0.8 * 10
    assert stats.percentile(values, 95) == pytest.approx(48.0)
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0  # order does not matter


def test_percentile_matches_numpy_default():
    np = pytest.importorskip("numpy")
    values = list(np.random.default_rng(0).exponential(size=237))
    for q in (5, 50, 95, 99):
        assert stats.percentile(values, q) == pytest.approx(float(np.percentile(values, q)))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_the_drivers_interquartile_share():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 102.5)


def test_union_length_counts_overlaps_once():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert stats.union_length([(0, 10), (2, 3)]) == pytest.approx(10.0)
    assert stats.union_length([]) == 0.0


def test_heartbeat_keeps_late_ticks_and_finds_the_longest_in_a_stretch():
    from benchmarks.spans import Heartbeat

    beat = Heartbeat(period=0.001, late=10.0).start()
    beat.stop()  # stops and joins; no tick of a millisecond is ten seconds late
    assert beat.late_ticks == [] and beat.longest_ms(0.0, 1e9) == 0.0
    beat.late_ticks = [(1.0, 1.5), (2.0, 8.0), (9.0, 9.2)]
    assert beat.longest_ms(0.0, 1.2) == 500.0  # overlaps the first only
    assert beat.longest_ms(1.4, 9.1) == 6000.0
    assert beat.longest_ms(8.5, 8.9) == 0.0


def test_update_sign_flip_share_counts_the_weights_that_did_not_move_against_the_gradient():
    import numpy as np

    from benchmarks import correctness

    grads = {"norm": np.array([1.0, -2.0, 3.0, -4.0]), "input_layernorm": np.array([[5.0, -6.0]])}
    before = {"norm": np.zeros(4), "input_layernorm": np.full((1, 2), 0.25)}
    # the third weight moved with its gradient and the fourth stood still; the rest moved against theirs
    after = {"norm": np.array([-1e-5, 1e-5, 1e-5, 0.0]), "input_layernorm": np.array([[0.2, 0.3]])}
    assert correctness.update_sign_flip_share(before, after, grads) == pytest.approx(2 / 6)
    step = {"loss": 1.0, "grad_norm": 2.0, "before": before, "after": after}
    d = correctness.train_distances(step, {"loss": 1.0, "grad_norm": 2.0, "norm_grads": grads})
    assert not correctness.judge("trainer", d)  # a third wrong is far over the tolerance
    del step["before"], step["after"]  # a cell that does not ask for the check is judged on the two scalars
    assert correctness.judge("trainer", correctness.train_distances(step, {"loss": 1.0, "grad_norm": 2.0}))
