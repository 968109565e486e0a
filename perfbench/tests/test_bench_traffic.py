"""The one traffic generator: a poisson schedule is fixed by the seed and
keeps its rate."""

import numpy as np
import pytest

from perfbench import traffic


def _mix(**kw):
    return {"arrival": "poisson", "rate_per_s": 40.0, **kw}


def test_the_seed_fixes_the_schedule():
    a = traffic.poisson_offsets(_mix(), 20, np.random.default_rng(7))
    b = traffic.poisson_offsets(_mix(), 20, np.random.default_rng(7))
    c = traffic.poisson_offsets(_mix(), 20, np.random.default_rng(8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 3**20])
def test_arrivals_fill_the_window_at_the_mix_rate(seed):
    offsets = traffic.poisson_offsets(_mix(), 20,
                                      np.random.default_rng(seed))
    assert np.all(np.diff(offsets) > 0)
    assert 0 < offsets[0] and offsets[-1] < 20.0
    # 800 expected; a Poisson count lies within five deviations
    assert abs(len(offsets) - 800) < 5 * 800**0.5
    assert offsets[-1] > 19.0


def test_backlog_is_closed_loop_and_unknown_arrivals_are_refused():
    assert not traffic.is_open_loop({"arrival": "backlog"})
    assert traffic.is_open_loop({"arrival": "poisson"})
    with pytest.raises(ValueError):
        traffic.is_open_loop({"arrival": "uniform"})
    with pytest.raises(ValueError):
        traffic.poisson_offsets(_mix(rate_per_s=0), 10,
                                np.random.default_rng(0))
