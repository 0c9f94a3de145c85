"""Open-loop schedules: reproducible per seed, the same work for every
seed."""
import numpy as np
import pytest

from harness import arrivals

MMPP = dict(process="mmpp", rate=800, burst_factor=4, burst_fraction=0.25,
            mean_phase_s=0.5)
POISSON = dict(process="poisson", rate=30)


@pytest.mark.parametrize("traffic", [MMPP, POISSON], ids=["mmpp", "poisson"])
def test_same_seed_same_schedule(traffic):
    a = arrivals.schedule(traffic, 20.0, 2**31 + 7)
    b = arrivals.schedule(traffic, 20.0, 2**31 + 7)
    np.testing.assert_array_equal(a, b)
    c = arrivals.schedule(traffic, 20.0, 2**31 + 8)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("traffic", [MMPP, POISSON], ids=["mmpp", "poisson"])
def test_every_seed_gets_the_same_count_inside_the_window(traffic):
    counts = set()
    for seed in range(5):
        t = arrivals.schedule(traffic, 20.0, seed)
        assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 20.0
        counts.add(len(t))
    assert len(counts) == 1
    assert abs(counts.pop() - 20.0 * traffic["rate"]) <= 0.01 * 20 * traffic["rate"] + 2


def test_poisson_gaps_are_one_set_in_another_order():
    def gaps(seed):
        t = arrivals.poisson(30, 20.0, seed)
        return np.diff(np.concatenate([t, [20.0]]))

    a, b = gaps(1), gaps(2)
    assert len(a) == 600
    assert not np.allclose(a, b)
    np.testing.assert_allclose(np.sort(a), np.sort(b), rtol=1e-9)
    assert abs(np.mean(a) - 1 / 30) < 1e-9


def test_mmpp_bursts_run_at_the_burst_factor():
    t = arrivals.mmpp(800, 20.0, 3, burst_factor=4, burst_fraction=0.25,
                      mean_phase_s=0.5)
    # 20 calm + 20 burst phases, a quarter of the time at 4x the calm rate
    calm = 800 / (0.75 + 0.25 * 4)
    hist, _ = np.histogram(t, bins=np.arange(0, 20.05, 0.05))
    rates = hist / 0.05
    assert rates.max() > 2.5 * calm
    assert np.percentile(rates, 25) < 1.5 * calm
