"""Arrival schedules of the open-loop traffic mixes, from a seed.

Every seed gets the same work: the same number of arrivals, the same set of
gaps or phases, in an order the seed draws. So two seeds differ in where
the bursts fall, not in how much load a window carries.

``poisson``: ``round(rate * seconds)`` arrivals whose gaps are the
exponential distribution's quantiles at ``(j + 0.5) / n``, shuffled.

``mmpp``: the two-state Markov-modulated Poisson process of the
repository's ``benchmarks/traffic_o1.bursty_arrivals`` (calm and burst
phases of exponential length, mean ``mean_phase_s`` a pair, a share
``burst_fraction`` of the time in bursts at ``burst_factor`` times the calm
rate, long-run mean ``rate``). Here the phase lengths are the exponential
quantiles of each state, shuffled; each phase holds ``round(rate * length)``
arrivals, placed uniformly at random inside it (a Poisson process given its
count).
"""
from __future__ import annotations

import numpy as np


def _quantiles(n: int, mean: float) -> np.ndarray:
    return -mean * np.log(1.0 - (np.arange(n) + 0.5) / n)


def poisson(rate: float, seconds: float, seed: int) -> np.ndarray:
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(seed).permutation(_quantiles(n, 1.0))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())  # the last gap ends the window


def mmpp(rate: float, seconds: float, seed: int, *, burst_factor: float,
         burst_fraction: float, mean_phase_s: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    calm_rate = rate / (1.0 - burst_fraction + burst_fraction * burst_factor)
    pairs = max(1, int(round(seconds / (2.0 * mean_phase_s))))
    calm = rng.permutation(
        _quantiles(pairs, 2.0 * (1.0 - burst_fraction) * mean_phase_s))
    burst = rng.permutation(
        _quantiles(pairs, 2.0 * burst_fraction * mean_phase_s))
    lengths = np.stack([calm, burst], axis=1).reshape(-1)
    lengths *= seconds / lengths.sum()
    rates = np.tile([calm_rate, calm_rate * burst_factor], pairs)
    starts = np.concatenate([[0.0], np.cumsum(lengths)[:-1]])
    out = [s + np.sort(rng.uniform(0.0, ln, int(round(r * ln))))
           for s, ln, r in zip(starts, lengths, rates)]
    return np.concatenate(out)


def schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix."""
    if traffic["process"] == "poisson":
        return poisson(traffic["rate"], seconds, seed)
    if traffic["process"] == "mmpp":
        return mmpp(traffic["rate"], seconds, seed,
                    burst_factor=traffic["burst_factor"],
                    burst_fraction=traffic["burst_fraction"],
                    mean_phase_s=traffic["mean_phase_s"])
    raise ValueError(f"unknown arrival process {traffic['process']!r}")
