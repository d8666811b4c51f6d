"""Open-loop arrival schedules, fixed from the seed before serving starts.

``_poisson_times``, ``_pareto_burst_times`` and ``arrival_times`` are
copied from ``benchmarks/loadgen.py`` (the repository's seeded open-loop
generator), so that the yardstick does not move when that file does.

``schedule`` is what the benchmark runs. It draws one set of inter-arrival
gaps from the traffic file's own ``shape_seed`` and lets the run's seed
only reorder them: every seed offers the same number of requests over the
same span, with the same gaps in another order. A seed thus changes which
request waits where, never how much work a run holds.
"""

from __future__ import annotations

import numpy as np

ARRIVAL_PATTERNS = ("poisson", "pareto_burst")


def _poisson_times(rng: np.random.Generator, rate: float,
                   duration_s: float) -> np.ndarray:
    n = max(1, int(rate * duration_s * 1.5) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < duration_s:                       # top up the tail
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < duration_s]


def _pareto_burst_times(rng: np.random.Generator, rate: float,
                        duration_s: float,
                        alpha: float = 1.5) -> np.ndarray:
    """Heavy-tail renewal gaps: Pareto(alpha) scaled to mean
    ``1/rate`` (alpha > 1 so the mean exists). Low alpha = burstier."""
    if alpha <= 1.0:
        raise ValueError("alpha must be > 1 (finite mean)")
    scale = (alpha - 1.0) / alpha / rate             # mean = 1/rate
    n = max(1, int(rate * duration_s * 1.5) + 16)
    t = np.cumsum(scale * (rng.pareto(alpha, n) + 1.0))
    while t[-1] < duration_s:
        t = np.concatenate([t, t[-1] + np.cumsum(
            scale * (rng.pareto(alpha, n) + 1.0))])
    return t[t < duration_s]


def arrival_times(rng: np.random.Generator, pattern: str, rate: float,
                  duration_s: float, *, alpha: float = 1.5) -> np.ndarray:
    if pattern == "poisson":
        return _poisson_times(rng, rate, duration_s)
    if pattern == "pareto_burst":
        return _pareto_burst_times(rng, rate, duration_s, alpha)
    raise ValueError(f"unknown arrival pattern {pattern!r}; "
                     f"choose from {ARRIVAL_PATTERNS}")


def schedule(arrivals: dict, rate: float, duration_s: float,
             seed: int) -> np.ndarray:
    """Due times in ``[0, duration_s)``, ascending. ``arrivals`` is the
    traffic file's block: ``pattern``, ``shape_seed`` and, for bursts,
    ``alpha``."""
    shape = np.random.default_rng(arrivals["shape_seed"])
    times = arrival_times(shape, arrivals["pattern"], rate, duration_s,
                          alpha=arrivals.get("alpha", 1.5))
    gaps = np.diff(times, prepend=0.0)
    order = np.random.default_rng([int(seed), 1]).permutation(len(gaps))
    return np.cumsum(gaps[order])
