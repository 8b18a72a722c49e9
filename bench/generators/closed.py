"""Closed loop: one client sends ``batch`` queries at once through
``search_batch`` and sends the next batch when the last one returned.

Mix keys: ``batch``, ``pool``, ``draw`` (``bench/lib/loadgen.py``).
"""
from bench.lib import loadgen


def plan(mix, rng, seconds):
    return loadgen.Plan(loop="closed", batch=int(mix["batch"]), pool=int(mix["pool"]),
                        picks=loadgen.picks(mix, rng))
