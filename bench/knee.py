#!/usr/bin/env python3
"""Sweep the arrival rate of an open-loop cell to find its knee: the highest
rate at which nothing is shed and the queue does not grow across the window.

    python3 bench/knee.py --workload glove-fw.poisson --seed 1 \\
        --rates 800,1000,1200,1400,1600 --seconds 15

One process, one set-up; each rate runs the cell's own mix (its draw, pool
and arrival law) at that rate for ``--seconds``.  Per rate it prints the
answered rate, the shed count, p50/p95 from when each request was due, and
the p95 of the last quarter of the window against the first (a growing
queue shows there).  The cell's traffic file then carries a fixed rate.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import contextlib  # noqa: E402

import numpy as np  # noqa: E402

from bench.lib import corpus as corpus_mod, harness, loadgen, registry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = registry.resolve(args.workload, ROOT)
    harness.require_chips(cell.chips)
    cfg, mix = cell.config, cell.traffic
    corpus = np.asarray(corpus_mod.make_corpus(args.seed, cfg["corpus"]))
    pool_q = corpus[corpus_mod.pool_rows(args.seed, len(corpus), int(mix["pool"]))]
    svc = harness.make_service(cfg, corpus)
    warm = pool_q[np.arange(int(cfg["service"]["max_batch"])) % len(pool_q)]
    svc.search_batch(warm)
    svc.start_async()
    for f in [svc.search_async(q) for q in warm[:8]]:
        f.result(timeout=600)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = loadgen.plan(dict(mix, rate_qps=rate), args.seed, args.seconds)
        before = svc.async_launches
        win = harness.run_open(svc, pool_q, plan, args.seconds, lambda n: contextlib.nullcontext())
        ok = np.isfinite(win.done)
        lat = (win.done - win.due) * 1e3
        q = args.seconds / 4
        first, last = ok & (win.due < q), ok & (win.due >= 3 * q)
        print(json.dumps({
            "rate_qps": rate, "requests": len(win.due), "answered_qps": ok.sum() / win.seconds,
            "shed": int(win.shed.sum()), "p50_ms": float(np.percentile(lat[ok], 50)),
            "p95_ms": float(np.percentile(lat[ok], 95)),
            "p95_first_quarter_ms": float(np.percentile(lat[first], 95)),
            "p95_last_quarter_ms": float(np.percentile(lat[last], 95)),
            "rows_per_launch": ok.sum() / max(1, svc.async_launches - before),
            "lateness_p95_ms": float(np.percentile((win.sent - win.due) * 1e3, 95)),
        }), flush=True)
    svc.stop_async()
    return 0


if __name__ == "__main__":
    sys.exit(main())
