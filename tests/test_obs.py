"""repro.obs: spans cost nothing and format nothing while no profiler
capture runs, land in the capture with their ids while one does, and the
latency histogram keeps every sample's bucket in constant memory."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs


class Unprintable:
    """An id that fails the run if anything formats it."""

    def __str__(self):
        raise AssertionError("span ids were formatted")

    __repr__ = __str__


def test_span_off_is_shared_noop_and_formats_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    ctx = obs.span("ann.launch", launch=Unprintable())
    assert ctx is obs.span("ann.dispatch")
    with ctx as c:
        c.set_metadata(rows=Unprintable())


def test_span_on_lands_in_the_capture_with_its_ids(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("ann.launch", launch=7, rows=3) as s:
            s.set_metadata(last_req=41)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    found = [
        dict(ev.stats)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events if ev.name == "ann.launch"
    ]
    assert found == [{"launch": 7, "rows": 3, "last_req": 41}]


def test_histogram_percentiles_within_half_a_bucket():
    rng = np.random.default_rng(0)
    x = rng.lognormal(mean=np.log(0.05), sigma=1.0, size=20000)
    h = obs.LatencyHistogram()
    for v in x:
        h.add(float(v))
    assert h.n == len(x)
    for q in (1, 50, 95, 99, 99.9):
        exact = np.sort(x)[int(np.ceil(q / 100 * len(x))) - 1]
        assert h.percentile(q) == pytest.approx(exact, rel=0.019)


def test_histogram_keeps_an_early_tail_in_constant_memory():
    """A ring of the last 1,024 samples would have dropped the slow start."""
    h = obs.LatencyHistogram()
    buckets = len(h.counts)
    for _ in range(200):
        h.add(2.0)
    for _ in range(100_000):
        h.add(0.001)
    assert len(h.counts) == buckets
    assert h.percentile(99.9) == pytest.approx(2.0, rel=0.019)
    assert h.percentile(50) == pytest.approx(0.001, rel=0.019)


def test_histogram_edges_and_clear():
    h = obs.LatencyHistogram()
    assert h.percentile(50) is None
    h.add(0.0)        # below the first edge: the first bucket
    h.add(1e6)        # above the last edge: the last bucket
    assert h.percentile(1) < 2e-6 and h.percentile(100) > 900.0
    h.clear()
    assert h.n == 0 and h.percentile(50) is None and not any(h.counts)
