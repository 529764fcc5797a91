"""Percentile and rate arithmetic, and the traffic generator."""
import math

import numpy as np
import pytest

from bench.harness import stats, traffic

DECODE = {"loop": "open", "rate_per_s": 4.0, "max_batch": 8,
          "prompt_frames": {"dist": "uniform", "lo": 8, "hi": 16},
          "new_frames": {"dist": "uniform", "lo": 32, "hi": 64}}
SCORE = dict(DECODE, prompt_frames={
    "dist": "lognormal", "median": 48, "sigma": 0.6, "round_up": 16,
    "lo": 16, "hi": 256}, new_frames={"dist": "fixed", "value": 0})


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 99) == 99
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([], 90) is None
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile(v, 0)


def test_rate_over_a_window():
    assert stats.rate(300, 2.0) == 150.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


@pytest.mark.parametrize("mix", [DECODE, SCORE])
def test_open_loop_is_deterministic_per_seed(mix):
    a = traffic.open_loop(mix, 2 ** 31 + 3, 10.0, 8)
    b = traffic.open_loop(mix, 2 ** 31 + 3, 10.0, 8)
    assert len(a) == len(b) == 40
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.new_frames == y.new_frames
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", [DECODE, SCORE])
def test_seeds_offer_the_same_work_in_another_order(mix):
    a = traffic.open_loop(mix, 1, 10.0, 8)
    b = traffic.open_loop(mix, 2, 10.0, 8)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.new_frames for r in a) == sorted(r.new_frames for r in b)
    gaps = lambda rs: sorted(np.diff([0.0] + [r.due_s for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the whole schedule falls inside the window, in due order
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    assert a[-1].due_s < 10.0 * 1.2


def test_length_quantiles():
    assert traffic.quantile_sizes({"dist": "uniform", "lo": 8, "hi": 16},
                                  9) == list(range(8, 17))
    s = traffic.quantile_sizes(SCORE["prompt_frames"], 500)
    assert min(s) >= 16 and max(s) <= 256
    assert all(x % 16 == 0 for x in s)
    assert s[249] == 48 and s[250] == 64  # the median, rounded up
    assert traffic.quantile_sizes({"dist": "fixed", "value": 0}, 3) == [0] * 3
    with pytest.raises(ValueError):
        traffic.quantile_sizes({"dist": "zipf"}, 3)


def test_arrivals_are_poisson_quantiles():
    due = traffic.arrivals(5.0, 1000, seed=9)
    gaps = np.diff([0.0] + due)
    assert np.mean(gaps) == pytest.approx(0.2, rel=0.01)
    assert sorted(gaps)[500] == pytest.approx(math.log(2) / 5.0, rel=0.01)


def test_check_mix_refuses_what_it_cannot_read():
    traffic.check_mix(DECODE)
    traffic.check_mix({"loop": "closed", "batch": 64, "frames": 300})
    with pytest.raises(ValueError):
        traffic.check_mix(dict(DECODE, loop="bursty"))
    with pytest.raises(ValueError):
        traffic.check_mix(dict(DECODE, rate_per_s=0))
