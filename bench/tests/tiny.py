"""A checkout-shaped copy of the benchmark at a size a CPU test run holds."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_root(dst: str, n_docs: int = 4096, pool: int = 64, rate_qps: float = 50.0) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` into ``dst`` and shrink every
    configuration and traffic mix: corpus rows, query pool, arrival rate and
    the reference's sample and block."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(
        os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    cdir = os.path.join(dst, "bench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["corpus"]["n_docs"] = n_docs
        cfg["check"].update(sample=16, block=n_docs)
        with open(path, "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(dst, "bench", "traffic")
    for name in os.listdir(tdir):
        path = os.path.join(tdir, name)
        with open(path) as f:
            mix = json.load(f)
        mix["pool"] = pool
        if "rate_qps" in mix:
            mix["rate_qps"] = rate_qps
        with open(path, "w") as f:
            json.dump(mix, f)
    return dst
