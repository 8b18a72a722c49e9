#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds of the program, of
its precision control and of the planted faults, in one process so set-up
is paid once per seed and compiles once.

    python3 bench/readings.py --workload glove-fw.bulk --seeds 1,2,3 \\
        --control-seeds 4,5,6 --faults alter:7,half:8 --seconds 5 \\
        [--out readings.jsonl]

Each program run is a full run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the comparison with the reference).
A ``--control-seeds`` run puts the configuration's ``control`` in the
program's place: the plain reference one precision step below the stated
one (``bench/lib/check.py::control_answers``), judged on the same sample.
A ``--faults`` run (``fault:seed``) is a program run with the timed path
broken underneath (``harness.plant_fault``).  One JSON line per run goes to
stdout and to ``--out``; the benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import harness, registry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="", help="fault:seed,... (alter, half)")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = registry.resolve(args.workload, ROOT)
    harness.require_chips(cell.chips)
    runs = [(int(s), False, None) for s in args.seeds.split(",") if s]
    runs += [(int(s), True, None) for s in args.control_seeds.split(",") if s]
    runs += [(int(f.split(":")[1]), False, f.split(":")[0]) for f in args.faults.split(",") if f]
    for seed, control, fault in runs:
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t0, control=control, fault=fault)
        line = json.dumps({"workload": cell.name, "seed": seed, "control": control, "fault": fault,
                           "correct": res["correct"], "check": res["check"],
                           "metrics": res["metrics"], "attempted": res["attempted"],
                           "failed": res["failed"], "device": res["device"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
