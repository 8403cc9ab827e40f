"""The traffic generator's schedules, from a seed."""

import numpy as np
import pytest

from loadgen import arrival_offsets, pool_size

SEED = 2**31 + 977          # seeds run past 32 signed bits


def _rng(seed=SEED):
    return np.random.default_rng(np.random.SeedSequence(seed))


@pytest.mark.parametrize("rate,seconds", [(400, 20.0), (37, 3.0)])
def test_poisson_schedule_is_fixed_by_the_seed(rate, seconds):
    mix = {"loop": "open", "arrival": "poisson", "rate_per_s": rate,
           "rows": 1}
    a = arrival_offsets(mix, seconds, _rng())
    b = arrival_offsets(mix, seconds, _rng())
    c = arrival_offsets(mix, seconds, _rng(SEED + 1))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # every seed brings the same number of requests, inside the window
    assert len(a) == len(c) == round(rate * seconds)
    assert np.all(np.diff(a) >= 0)
    assert a[0] >= 0 and a[-1] < seconds


def test_poisson_gaps_look_exponential():
    mix = {"loop": "open", "arrival": "poisson", "rate_per_s": 500,
           "rows": 1}
    gaps = np.diff(arrival_offsets(mix, 40.0, _rng()))
    assert abs(gaps.mean() - 1 / 500) < 0.05 / 500
    # exponential: the standard deviation equals the mean
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05


@pytest.mark.parametrize("rows,seconds", [(1, 20.0), (128, 20.0)])
def test_open_loop_pool_holds_one_block_per_request(rows, seconds):
    """An open loop's pool has a distinct query block for every
    request due in the window, up to its cap."""
    mix = {"loop": "open", "arrival": "poisson", "rate_per_s": 560,
           "rows": rows}
    assert pool_size(mix, seconds) == len(arrival_offsets(mix, seconds,
                                                          _rng()))


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError):
        arrival_offsets({"rate_per_s": 1, "arrival": "zipf"}, 1.0, _rng())


def test_pool_sizes():
    assert pool_size({"loop": "open", "rate_per_s": 400}, 20) == 8000
    assert pool_size({"loop": "open", "rate_per_s": 4000}, 20) == 16384
    assert pool_size({"loop": "closed", "pool_requests": 64}, 20) == 64
