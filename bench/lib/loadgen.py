"""Turns a traffic mix (a data file) and a seed into the requests of a run.

A mix is a JSON object.  Its ``generator`` names the module that plans the
requests, ``bench/generators/<generator>.py``, found by name like every
other part of a cell; the rest are that generator's parameters.  Keys every
generator reads through ``picks``:

  * ``pool``: how many distinct corpus rows queries are drawn from;
  * ``draw``: ``"uniform"`` over the pool, or ``"zipf"`` with exponent
    ``zipf_s`` (rank r drawn with weight r^-s; which pool entry holds which
    rank is itself drawn from the seed).

A generator module has ``plan(mix, rng, seconds) -> Plan``.  The harness
carries out whatever the plan holds: a closed or an open loop of queries,
and writer operations at their due times.  Everything is drawn from the
``numpy.random.Generator`` it is handed, seeded with the run's seed, so one
seed always gives the same requests.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from bench.lib import registry


def zipf_sampler(rng: np.random.Generator, pool: int, s: float) -> Callable[[int], np.ndarray]:
    """Zipfian rank-frequency sampler over a query pool.  Copied from
    ``src/repro/launch/serve.py::zipf_sampler``; ranks are mapped onto pool
    entries by a seeded permutation so the hot set differs from seed to seed."""
    ranks = np.arange(1, pool + 1, dtype=np.float64)
    p = ranks ** -s
    p /= p.sum()
    order = rng.permutation(pool)
    return lambda n: order[rng.choice(pool, size=n, p=p)]


def picks(mix: Dict[str, Any], rng: np.random.Generator) -> Callable[[int], np.ndarray]:
    """The mix's draw: a function giving the pool indices of the next ``n``
    queries."""
    pool = int(mix["pool"])
    draw = mix.get("draw", "uniform")
    if draw == "uniform":
        return lambda n: rng.integers(0, pool, size=n)
    if draw == "zipf":
        return zipf_sampler(rng, pool, float(mix["zipf_s"]))
    raise ValueError(f"unknown draw {draw!r}")


@dataclasses.dataclass
class Plan:
    """The requests of one run.

    ``loop`` is ``"closed"`` (one client sends ``batch`` queries through
    ``search_batch`` and the next batch once the last returned) or
    ``"open"`` (single queries through ``search_async``, each sent at its
    ``due`` time in seconds from the window's start, whatever the service is
    doing).  ``picks`` draws the pool indices of the next ``n`` queries.
    ``ops`` are writer operations, ``(due seconds, op)``; the harness calls
    ``op(service)`` at its due time on a thread of its own."""

    loop: str
    batch: int
    pool: int
    picks: Callable[[int], np.ndarray]
    due: Optional[np.ndarray] = None
    ops: Sequence[Tuple[float, Callable[[Any], Any]]] = ()


def plan(mix: Dict[str, Any], seed: int, seconds: float, root: str = registry.ROOT) -> Plan:
    """The plan of the mix's generator for this seed and window."""
    gen = registry.load_module(os.path.join(root, "bench", "generators", mix["generator"] + ".py"))
    p = gen.plan(mix, np.random.default_rng([seed, 2]), seconds)
    if p.loop not in ("closed", "open"):
        raise ValueError(f"generator {mix['generator']!r} planned an unknown loop {p.loop!r}")
    return p
