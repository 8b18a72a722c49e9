"""The program's spans and counters, read back the way ``bench/spans.py``
reads them: a tiny ``AnnService`` (sync and async) and ``IndexWriter``
under a CPU profiler capture, the reductions on hand-made events, and a
whole traced run at a tiny size."""
import dataclasses
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny
from bench.lib import harness, program_spans as ps, registry, tracing
from repro.core import packed
from repro.core.segments import IndexWriter
from repro.core.types import FakeWordsConfig
from repro.serve.ann_service import AnnService, AnnServiceConfig

TABLE = {"ann.enqueue", "ann.queue_wait", "ann.coalesce", "ann.launch", "ann.dispatch",
         "ann.handoff", "ann.resolve", "writer.flush", "writer.refresh"}


def traced(log_dir, fn):
    """Run ``fn`` inside a capture and a window span; the wide event list."""
    with tracing.capture(log_dir):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            fn()
    return ps.load_events(log_dir)


def named(events, name):
    return sorted((e for e in events if ps.is_program(e) and e["name"] == name),
                  key=lambda e: e["start_ns"])


def contains(outer, inner):
    return (outer["start_ns"] <= inner["start_ns"]
            and inner["start_ns"] + inner["dur_ns"] <= outer["start_ns"] + outer["dur_ns"])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A segmented service driven through every path once, traced: a sync
    batch of 12 rows (two launches of max_batch 8), ten async requests, then
    an add, a refresh and a search of the new snapshot."""
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(340, 32)).astype(np.float32)
    q = rng.normal(size=(16, 32)).astype(np.float32)
    w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None, use_kernel=False)
    w.add(rows[:300])
    svc = AnnService(writer=w, service=AnnServiceConfig(
        k=5, depth=20, max_batch=8, max_wait_s=0.005, queue_depth=64))
    svc.search_batch(q[:8])
    waits = {}

    def work():
        svc.search_batch(q[:12])
        svc.start_async()
        before = (svc.queue_wait_s, svc.async_requests)
        for f in [svc.search_async(q[i]) for i in range(10)]:
            f.result(timeout=120)
        waits["queue_wait_s"] = svc.queue_wait_s - before[0]
        waits["async_requests"] = svc.async_requests - before[1]
        svc.stop_async()
        w.add(rows[300:])
        svc.refresh()
        svc.search_batch(q[:8])

    events = traced(str(tmp_path_factory.mktemp("served")), work)
    return events, waits


def test_every_span_of_the_table_appears(served):
    events, _ = served
    names = {e["name"] for e in events if ps.is_program(e)}
    assert TABLE <= names
    assert names & {"packed.pack", "packed.append"}


def test_launch_children_nest_on_the_same_line(served):
    events, _ = served
    launches = {e["args"]["launch"]: e for e in named(events, "ann.launch")}
    for child in named(events, "ann.dispatch") + named(events, "ann.handoff"):
        parent = launches[child["args"]["launch"]]
        assert parent["thread"] == child["thread"] and contains(parent, child)
    sync = [e for e in named(events, "ann.launch") if "first_req" not in e["args"]]
    assert [e["args"]["rows"] for e in sync[:2]] == [8, 4]


def test_async_launches_run_on_the_batcher_line(served):
    events, _ = served
    enqueued = named(events, "ann.enqueue")
    callers = {e["thread"] for e in enqueued}
    async_launches = [e for e in named(events, "ann.launch") if "first_req" in e["args"]]
    batcher = {e["thread"] for e in async_launches}
    assert len(batcher) == 1 and not batcher & callers
    for name in ("ann.queue_wait", "ann.coalesce", "ann.resolve"):
        assert {e["thread"] for e in named(events, name)} == batcher
    ids = {e["args"]["launch"] for e in async_launches}
    for name in ("ann.coalesce", "ann.resolve"):
        assert {e["args"]["launch"] for e in named(events, name)} <= ids
    assert len(enqueued) == 10
    for e in enqueued:
        r = e["args"]["req"]
        assert any(a["args"]["first_req"] <= r <= a["args"]["last_req"] for a in async_launches)


def test_queue_wait_counters_agree_with_enqueue_and_launch(served):
    """Each request waits from its enqueue to the start of the launch that
    carries it; the counters sum those waits."""
    events, waits = served
    async_launches = [e for e in named(events, "ann.launch") if "first_req" in e["args"]]
    from_spans = []
    for e in named(events, "ann.enqueue"):
        r = e["args"]["req"]
        launch = next(a for a in async_launches
                      if a["args"]["first_req"] <= r <= a["args"]["last_req"])
        from_spans.append((launch["start_ns"] - e["start_ns"]) / 1e9)
    assert waits["async_requests"] == len(from_spans) == 10
    # The counters take the host clock a statement inside each span, and a
    # thread switch can fall between the two.
    assert waits["queue_wait_s"] == pytest.approx(sum(from_spans), abs=1e-3 * len(from_spans))


def test_queue_wait_counts_a_held_launch():
    """Requests enqueued while the service lock is held wait at least as
    long as it is held, and the launch that carries them counts it."""
    rng = np.random.default_rng(1)
    w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None, use_kernel=False)
    w.add(rng.normal(size=(200, 32)).astype(np.float32))
    svc = AnnService(writer=w, service=AnnServiceConfig(k=5, depth=20, max_batch=8))
    q = rng.normal(size=(3, 32)).astype(np.float32)
    svc.search_batch(q)
    svc.start_async()
    try:
        with svc._lock:
            t0 = time.perf_counter()
            futs = [svc.search_async(x) for x in q]
            time.sleep(0.05)
        for f in futs:
            f.result(timeout=60)
        elapsed = time.perf_counter() - t0
    finally:
        svc.stop_async()
    assert svc.async_requests == 3
    assert 0.045 * 3 <= svc.queue_wait_s <= elapsed * 3
    assert svc.stats()["queue_wait_s"] == svc.queue_wait_s


def test_refresh_emits_writer_and_pack_spans(served):
    events, _ = served
    (refresh,) = named(events, "writer.refresh")
    flushes = named(events, "writer.flush")
    assert any(refresh["thread"] == f["thread"] and contains(refresh, f) for f in flushes)
    assert refresh["args"]["rows"] == 340 and refresh["args"]["segments"] == 1
    packs = named(events, "packed.pack") + named(events, "packed.append")
    dispatches = named(events, "ann.dispatch")
    assert packs and all(p["start_ns"] > refresh["start_ns"] for p in packs)
    assert all(any(contains(d, p) for d in dispatches) for p in packs)


def test_compile_span_on_a_miss_only(tmp_path):
    rng = np.random.default_rng(2)
    # A row width no other test packs, so the first search misses the cache.
    w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None, use_kernel=False)
    w.add(rng.normal(size=(117, 37)).astype(np.float32))
    reader = w.refresh()
    q = jnp.asarray(rng.normal(size=(4, 37)).astype(np.float32))
    before = packed.EXEC_CACHE.compiles

    def work():
        reader.search(q, k=5, depth=20, packed=True)
        with jax.profiler.TraceAnnotation("bench.second"):
            reader.search(q, k=5, depth=20, packed=True)

    events = traced(str(tmp_path), work)
    compiles = named(events, "packed.compile")
    assert packed.EXEC_CACHE.compiles - before == len(compiles) == 1
    assert compiles[0]["args"]["kind"] == "search"
    (second,) = [e for e in events if e["name"] == "bench.second"]
    assert compiles[0]["start_ns"] < second["start_ns"]


# -- the reductions, on hand-made events ---------------------------------------

D0 = "/device:TPU:0"


def ev(name, start, dur, plane="/host:CPU", line="python", thread=None, **args):
    e = {"plane": plane, "line": line, "name": name, "start_ns": float(start), "dur_ns": float(dur)}
    if thread is not None:
        e.update(thread=thread, args=args)
    return e


@pytest.fixture
def events():
    # Window [0, 1000).  Device ops run [100, 400) and [600, 900).  The
    # batcher (thread 0/1) waits for work over [0, 50), launches over
    # [50, 450) (dispatch [50, 100), hand-off [100, 420)) and [450, 950)
    # (dispatch [450, 600)), and resolves [950, 980).
    return [
        ev("bench.window", 0, 1000),
        ev("bench.idle", 0, 1000, line="python3"),
        ev("fusion", 100, 300, plane=D0, line=tracing.OPS_LINE),
        ev("fusion", 600, 300, plane=D0, line=tracing.OPS_LINE),
        ev("ann.queue_wait", 0, 50, thread="0/1"),
        ev("ann.launch", 50, 400, thread="0/1", launch=0, rows=8),
        ev("ann.dispatch", 50, 50, thread="0/1", launch=0),
        ev("ann.handoff", 100, 320, thread="0/1", launch=0),
        ev("ann.launch", 450, 500, thread="0/1", launch=1, rows=8),
        ev("ann.dispatch", 450, 150, thread="0/1", launch=1),
        ev("ann.resolve", 950, 30, thread="0/1", launch=1),
        ev("ann.enqueue", 460, 5, thread="0/2", req=3),
    ]


def test_dispatch_ms_is_the_mean_dispatch(events):
    assert ps.dispatch_ms(events) == pytest.approx((50 + 150) / 2 / 1e6)


def test_idle_in_launch(events):
    # Idle: [0, 100), [400, 600), [900, 1000).  Under a launch: [50, 100),
    # [400, 450) and [450, 600), [900, 950): 50 + 200 + 50 = 300 of 1000.
    assert ps.idle_in_launch_pct(events) == pytest.approx(30.0)


def test_idle_under_program_spans(events):
    # 400 ns idle; uncovered only [980, 1000).
    assert ps.idle_under_program_pct(events) == pytest.approx(100.0 * 380 / 400)


def test_idle_gaps_named_by_innermost_program_span(events):
    gaps = ps.idle_gaps_program(events)
    # [400, 600): midpoint 500 lies in launch 1's dispatch (the enqueue on
    # another line closed at 465); [0, 100): midpoint 50 opens the first
    # launch and its dispatch, the dispatch started no earlier and is
    # shorter; [900, 1000): midpoint 950 starts the resolve.
    assert gaps == [["ann.dispatch", pytest.approx(200e-9)],
                    ["ann.dispatch", pytest.approx(100e-9)],
                    ["ann.resolve", pytest.approx(100e-9)]]
    late = [e for e in events if e["name"] != "ann.resolve"]
    assert ps.idle_gaps_program(late)[2][0] == ps.NO_SPAN


def test_queue_wait_ms_from_counters():
    assert ps.queue_wait_ms({"queue_wait_s": 0.5, "async_requests": 10}) == pytest.approx(50.0)
    assert ps.queue_wait_ms({"queue_wait_s": 0.0, "async_requests": 0}) is None


def test_tracing_reductions_ignore_program_spans(events):
    narrow = [e for e in events if not ps.is_program(e)]
    for f in (tracing.busy_ns, tracing.idle_pct, tracing.top_ops, tracing.idle_gaps):
        assert f(events) == f(narrow)


# -- the whole traced run, at a tiny size ---------------------------------------


@pytest.mark.parametrize("name", ["glove-fw.poisson", "glove-fw.bulk"])
def test_traced_run_reads_every_new_number(tmp_path, name):
    root = tiny.make_root(str(tmp_path))
    cell = registry.resolve(name, root)
    out = str(tmp_path / "out")
    harness_own = (tracing.load_events, harness.counters, harness.per_layer,
                   harness.run_closed, harness.run_open)
    result, got = ps.traced_run(cell, 2**33 + 7, 1.0, time.perf_counter(), out)
    assert (tracing.load_events, harness.counters, harness.per_layer,
            harness.run_closed, harness.run_open) == harness_own
    assert result["correct"]
    assert got["per_layer_without_program_spans"] == {
        k: v["value"] for k, v in result["metrics"].items()}
    new = got["new_per_layer"]
    split = name.split(".")[1]
    assert set(new) == {f"dispatch_ms.{split}", f"idle_in_launch_pct.{split}"} | (
        {"queue_wait_ms"} if split == "poisson" else set())
    assert new[f"dispatch_ms.{split}"] > 0
    assert got["span_count"]["ann.launch"] == got["counters"]["batches"] > 0
    if split == "poisson":
        assert new["queue_wait_ms"] > 0 and got["counters"]["async_requests"] > 0
        assert got["queue_wait_launch_resolve_ms"] > 0 and got["sent_to_done_ms"] > 0
    else:
        assert "queue_wait_ms" not in new and got["counters"]["async_requests"] == 0
    assert glob.glob(os.path.join(out, f"{name}.{2**33 + 7}", "**", "*.xplane.pb"),
                     recursive=True)


def test_span_cost_is_measured_off_and_on():
    assert set(ps.span_cost_ns(200)) == {"off", "on", "empty_loop"}


# -- a recorded chip trace with program spans ------------------------------------

RECORDED = os.path.join(tiny.ROOT, "bench", "tests", "data", "trace_fw_poisson_spans.json")


def test_recorded_chip_trace_with_program_spans():
    """The first 0.5 s of a traced glove-fw.poisson window (TPU v5 lite), as
    ``program_spans.load_events`` keeps it: seven launches of 4 to 65 rows,
    each running ``jit_packed_search`` after the eager normalise and encode
    executables of its dispatch."""
    with open(RECORDED) as f:
        events = json.load(f)
    narrow = [e for e in events if not ps.is_program(e)]
    for f in (tracing.busy_ns, tracing.idle_pct, tracing.top_ops, tracing.idle_gaps):
        assert f(events) == f(narrow)
    cell = registry.resolve("glove-fw.bulk", tiny.ROOT)
    counters = {"queries": 332, "batches": 7, "async_launches": 7, "rejected": 0,
                "exec_cache_compiles": 0, "backend_compiles": 0}
    ctx = harness.MetricContext(cell=cell, events=events, counters=counters,
                                peaks=registry.peaks("TPU v5 lite", tiny.ROOT))
    wide = harness.per_layer(cell, ctx)
    assert {"search_ms", "topk_roofline_pct", "idle_pct.bulk"} <= set(wide)
    assert wide == harness.per_layer(cell, dataclasses.replace(ctx, events=narrow))

    work = registry.work_module(cell, "fused_topk")
    mods = tracing.modules_containing(events, work.TRACE_NAME)
    assert len(mods) == 7 and all(m["name"].startswith("jit_packed_search(") for m in mods)
    dispatches = named(events, "ann.dispatch")
    assert len(named(events, "ann.launch")) == len(dispatches) == 7
    for m in mods:
        assert any(contains(d, dict(m, dur_ns=0.0)) for d in dispatches)

    assert 4.0 < ps.dispatch_ms(events) < 8.0
    assert 0.0 < ps.idle_in_launch_pct(events) < tracing.idle_pct(events)
    assert 80.0 < ps.idle_under_program_pct(events) <= 100.0
    gaps = ps.idle_gaps_program(events)
    # The batcher's wait for the first request began before the capture, so
    # no span covers the window's first gap; the next longest are each
    # launch's hand-off tail, after its executable ends.
    assert gaps[0][0] == ps.NO_SPAN
    assert [g[0] for g in gaps[1:7]] == ["ann.handoff"] * 6
