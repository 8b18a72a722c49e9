#!/usr/bin/env python3
"""Run one cell traced, with the program's own spans and counters read.

    python3 bench/spans.py --workload glove-fw.poisson --seed 1 --seconds 20 [--out spans_out]

The run is ``bench/run.py --trace 1`` (``harness.run``), with the trace's
loader and the counters widened to the program's spans and its queue
counters (``bench/lib/program_spans.py::traced_run``).  Stdout ends with two
JSON lines: the harness's result line, then what the program's spans give
(``queue_wait_ms``, ``dispatch_ms``, ``idle_in_launch_pct``, the cell's
per-layer metrics read without the program spans, which must agree with the
result line's, the share of the device's idle time under some program span,
``idle_gaps_program``, the mean and count of each span; for an open loop,
the request latency against queue wait + launch + resolve) and the cost of
one span with the profiler off and on.  ``--out`` receives the raw capture.
Untraced numbers to compare with come from ``bench/run.py --trace 0``.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# As bench/run.py keeps it, unless the environment names a cache.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import harness, program_spans, registry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = registry.resolve(args.workload, ROOT)
    harness.require_chips(cell.chips)
    result, program = program_spans.traced_run(cell, args.seed, args.seconds, T_START, args.out)
    program["span_cost_ns"] = program_spans.span_cost_ns()
    harness.emit(result)
    print(json.dumps(program), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
