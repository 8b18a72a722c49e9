"""Open loop with Poisson arrivals: single queries due at exponential gaps
of mean ``1 / rate_qps``, sent when due whatever the service is doing.

Mix keys: ``rate_qps``, ``pool``, ``draw`` (``bench/lib/loadgen.py``).
"""
import numpy as np

from bench.lib import loadgen


def arrivals(rng: np.random.Generator, rate: float, seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson process at ``rate``."""
    n_max = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, size=n_max))
    while t[-1] < seconds:  # rare: extend the draw
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n_max))])
    return t[t < seconds]


def plan(mix, rng, seconds):
    pick = loadgen.picks(mix, rng)
    return loadgen.Plan(loop="open", batch=1, pool=int(mix["pool"]), picks=pick,
                        due=arrivals(rng, float(mix["rate_qps"]), seconds))
