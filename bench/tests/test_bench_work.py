"""The least-work arithmetic of the fused kernel and the roofline reader."""
import os
import types

import pytest

from bench.tests import tiny
from bench.lib import registry

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
N = 1_193_472


@pytest.fixture(scope="module")
def work():
    return registry.load_module(os.path.join(tiny.ROOT, "bench", "work", "fused_topk.py"))


def test_gemm_batch_of_256_is_compute_bound(work):
    t, bound = work.least_seconds("gemm", 256, N, 400, 2, V5E)
    assert bound == "compute"
    assert t == pytest.approx(2 * 256 * N * 400 / 197e12)
    assert 1.2e-3 < t < 1.3e-3


def test_lsh_counts_bytes_alone(work):
    t, bound = work.least_seconds("lsh", 256, N, 300, 4, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(N * 300 * 4 / 819e9)


def test_small_batch_is_bytes_bound(work):
    t, bound = work.least_seconds("gemm", 8, N, 400, 2, V5E)
    assert bound == "bytes" and t == pytest.approx(N * 400 * 2 / 819e9)


def _roofline(kernel_ns, launches, rows, n_docs):
    reader = registry.load_module(os.path.join(tiny.ROOT, "bench", "metrics", "topk_roofline_pct.py"))
    events = [{"plane": "/host:CPU", "line": "python", "name": "bench.window",
               "start_ns": 0.0, "dur_ns": 1e12, "module": None}]
    events += [{"plane": "/device:TPU:0", "line": "XLA Ops",
                "name": '%fused_topk.1 = (f32[8]) custom-call(), custom_call_target="tpu_custom_call"',
                "start_ns": 1e6 * i, "dur_ns": kernel_ns, "module": "m"} for i in range(launches)]
    cell = types.SimpleNamespace(
        root=tiny.ROOT,
        config={"work": {"kernel": "fused_topk", "mode": "gemm", "width": 400, "itemsize": 2},
                "corpus": {"n_docs": n_docs}},
    )
    ctx = types.SimpleNamespace(cell=cell, events=events, peaks=V5E,
                                counters={"batches": launches, "queries": rows * launches})
    return reader.read(ctx)


def test_roofline_counts_logical_work_not_padding():
    """Kernel time is all the reader takes from the trace; the padded bucket
    and padded columns never enter, so a kernel that stops padding gains."""
    least = 2 * 256 * N * 400 / 197e12
    pct = _roofline(kernel_ns=150e6, launches=4, rows=256, n_docs=N)
    assert pct == pytest.approx(100 * least / 0.150)
    assert _roofline(kernel_ns=75e6, launches=4, rows=256, n_docs=N) == pytest.approx(2 * pct)
