#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is looked up in ``BENCHMARK.json``.
The last line of stdout is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and ``check`` last); the last lines of stderr are the numbers compared, each
beside its limit.  Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.

JAX's persistent compilation cache is kept at ``.jax_cache/`` in the
checkout, whatever the environment says, so only a checkout's first run of
a cell compiles.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# Cache every executable, however quick to compile, so set-up is the same
# from the second run on.
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
