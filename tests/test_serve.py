"""Serving: continuous-batching engine + ANN service."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bruteforce, eval as ev, fakewords
from repro.core.types import FakeWordsConfig
from repro.models import transformer as tfm
from repro.serve.ann_service import AnnService, AnnServiceConfig
from repro.serve.engine import DecodeEngine, EngineConfig, Request

RNG = np.random.default_rng(11)


def _tiny():
    cfg = tfm.TransformerConfig(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    return cfg, tfm.init_params(jax.random.key(1), cfg)


def test_engine_matches_greedy_reference():
    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, EngineConfig(batch_slots=2, max_len=32, eos_id=1))
    prompt = RNG.integers(2, 64, 6).astype(np.int32)
    req = Request(uid=0, prompt=prompt, max_new_tokens=5)
    eng.submit(req)
    eng.run(max_steps=30)
    cur = list(prompt)
    ref = []
    for _ in range(5):
        _, lg = tfm.prefill(params, jnp.asarray(cur, jnp.int32)[None], cfg)
        nxt = int(jnp.argmax(lg[0]))
        ref.append(nxt)
        if nxt == 1:
            break
        cur.append(nxt)
    assert req.out_tokens[: len(ref)] == ref


def test_engine_continuous_batching_slot_reuse():
    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, EngineConfig(batch_slots=2, max_len=64, eos_id=0))
    reqs = [Request(uid=i, prompt=RNG.integers(2, 64, 4).astype(np.int32),
                    max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=60)
    assert all(r.done for r in reqs)          # all 5 served through 2 slots
    assert all(len(r.out_tokens) <= 3 for r in reqs)


def test_engine_isolation_between_concurrent_requests():
    """A request's output must not depend on what shares the batch."""
    cfg, params = _tiny()
    prompt = RNG.integers(2, 64, 6).astype(np.int32)
    # alone
    e1 = DecodeEngine(params, cfg, EngineConfig(batch_slots=2, max_len=32, eos_id=1))
    r_alone = Request(uid=0, prompt=prompt, max_new_tokens=4)
    e1.submit(r_alone)
    e1.run(max_steps=30)
    # with a neighbor
    e2 = DecodeEngine(params, cfg, EngineConfig(batch_slots=2, max_len=32, eos_id=1))
    r_shared = Request(uid=0, prompt=prompt, max_new_tokens=4)
    other = Request(uid=1, prompt=RNG.integers(2, 64, 9).astype(np.int32), max_new_tokens=4)
    e2.submit(r_shared)
    e2.submit(other)
    e2.run(max_steps=30)
    assert r_alone.out_tokens == r_shared.out_tokens


def test_engine_second_run_and_direct_step_drain():
    """Regression: run() compared the CUMULATIVE step counter against
    max_steps, so a second run() with work queued returned immediately; and
    requests retired via direct step() calls leaked (or double-returned) on
    the next run()."""
    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, EngineConfig(batch_slots=2, max_len=64, eos_id=0))
    r1 = Request(uid=0, prompt=RNG.integers(2, 64, 4).astype(np.int32),
                 max_new_tokens=3)
    eng.submit(r1)
    done1 = eng.run(max_steps=10)
    assert r1 in done1 and r1.done
    # retire a request via direct step() calls: run() must hand it back
    # exactly once, not leak it
    r2 = Request(uid=1, prompt=RNG.integers(2, 64, 4).astype(np.int32),
                 max_new_tokens=2)
    eng.submit(r2)
    while not r2.done:
        eng.step()
    done2 = eng.run(max_steps=10)
    assert done2 == [r2]
    # later run with the CUMULATIVE counter far past max_steps: must still
    # make progress (the bound applies to steps taken within the call)
    eng.steps = 10_000  # long-lived engine
    r3 = Request(uid=2, prompt=RNG.integers(2, 64, 4).astype(np.int32),
                 max_new_tokens=3)
    eng.submit(r3)
    done3 = eng.run(max_steps=10)
    assert r3 in done3 and r3.done
    assert eng.run(max_steps=10) == []  # drained: nothing to return


def test_ann_service_recall_and_batching(small_corpus):
    v = jnp.asarray(small_corpus)
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    svc = AnnService(idx, cfg, AnnServiceConfig(k=10, depth=100, rerank=True, max_batch=16))
    qs = small_corpus[:40]  # not a multiple of max_batch: exercises padding
    s, ids = svc.search_batch(qs)
    assert ids.shape == (40, 10)
    gt_s, gt_i = bruteforce.exact_topk(v, jnp.asarray(qs), 10)
    assert float(ev.recall_at(jnp.asarray(np.asarray(gt_i)), jnp.asarray(ids))) > 0.85
    assert svc.stats()["queries"] == 40


def test_ann_service_blockmax_pruned(small_corpus):
    """Blockmax-pruned serving: keeping half the blocks preserves most
    recall; keeping all blocks matches the unpruned service results."""
    v = jnp.asarray(small_corpus)
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    qs = small_corpus[:24]
    gt_s, gt_i = bruteforce.exact_topk(v, jnp.asarray(qs), 10)
    n_blocks = -(-v.shape[0] // 256)
    svc_all = AnnService(idx, cfg, AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=16, blockmax_keep=n_blocks))
    _, ids_all = svc_all.search_batch(qs)
    svc_half = AnnService(idx, cfg, AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=16,
        blockmax_keep=max(1, n_blocks // 2)))
    _, ids_half = svc_half.search_batch(qs)
    r_all = float(ev.recall_at(jnp.asarray(np.asarray(gt_i)), jnp.asarray(ids_all)))
    r_half = float(ev.recall_at(jnp.asarray(np.asarray(gt_i)), jnp.asarray(ids_half)))
    assert r_all > 0.85
    assert r_half > 0.3  # graceful degradation at beta=0.5
    assert r_all >= r_half


# -- async micro-batching loop (docs/DESIGN.md §14) --------------------------


def test_ann_service_async_matches_sync(small_corpus):
    """search_async results == search_batch results, request-for-request,
    and the micro-batcher coalesces singles into fewer launches."""
    v = jnp.asarray(small_corpus)
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    svc = AnnService(idx, cfg, AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=16, max_wait_s=0.05))
    qs = small_corpus[:24]
    s_ref, i_ref = svc.search_batch(qs)
    svc.start_async()
    futs = [svc.search_async(qs[i]) for i in range(24)]
    out = [f.result(timeout=30) for f in futs]
    svc.stop_async()
    s_async = np.concatenate([o[0] for o in out])
    i_async = np.concatenate([o[1] for o in out])
    np.testing.assert_array_equal(i_ref, i_async)
    np.testing.assert_allclose(s_ref, s_async, rtol=1e-5, atol=1e-6)
    st = svc.stats()
    # 24 singles coalesced under the 50ms window: strictly fewer launches
    # than requests, and per-request latency percentiles are recorded.
    assert 1 <= st["async_launches"] < 24
    assert st["req_p50_ms"] is not None and st["req_p99_ms"] is not None
    assert st["req_p99_ms"] >= st["req_p50_ms"]
    assert st["rejected"] == 0


def test_ann_service_async_backpressure(small_corpus):
    """A full admission queue rejects at the door (queue.Full) and counts
    the shed requests in stats()."""
    import queue as queue_mod

    v = jnp.asarray(small_corpus)
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    svc = AnnService(idx, cfg, AnnServiceConfig(
        k=5, depth=50, rerank=False, max_batch=1, max_wait_s=0.0,
        queue_depth=2))
    svc.start_async()
    rejected = 0
    futs = []
    with svc._lock:  # worker blocks on the service lock: queue backs up
        for i in range(32):
            try:
                futs.append(svc.search_async(small_corpus[i % 8]))
            except queue_mod.Full:
                rejected += 1
    assert rejected >= 1
    for f in futs:
        f.result(timeout=30)
    svc.stop_async()
    assert svc.stats()["rejected"] == rejected


def test_ann_service_async_with_nrt_refresh(small_corpus):
    """refresh() (a _bind swap) interleaves safely with the async worker;
    results always come from a coherent snapshot."""
    from repro.core.segments import IndexWriter

    cfg = FakeWordsConfig(quantization=50)
    w = IndexWriter(cfg, merge_policy=None, use_kernel=False)
    w.add(small_corpus[:500])
    svc = AnnService(writer=w, service=AnnServiceConfig(
        k=5, depth=50, rerank=False, max_batch=8, max_wait_s=0.005))
    svc.start_async()
    futs = [svc.search_async(small_corpus[i]) for i in range(8)]
    w.add(small_corpus[500:600])
    svc.refresh()
    futs += [svc.search_async(small_corpus[i]) for i in range(8, 16)]
    for f in futs:
        s, ids = f.result(timeout=30)
        assert ids.shape == (1, 5) and (ids >= 0).all()
    svc.stop_async()


def test_ann_service_segmented_blockmax(small_corpus):
    """Segmented blockmax serving rides the packed superbuffer: keeping
    every block matches the unpruned segmented service exactly."""
    from repro.core.segments import IndexWriter

    cfg = FakeWordsConfig(quantization=50)
    w = IndexWriter(cfg, merge_policy=None, use_kernel=False)
    w.add(small_corpus[:700])
    w.flush()
    w.add(small_corpus[700:1100])
    qs = small_corpus[:16]
    svc = AnnService(writer=w, service=AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=16))
    s0, i0 = svc.search_batch(qs)
    reader = svc.ann
    n_blocks = reader.packed_segments().bucket // 256
    svc_bm = AnnService(reader, service=AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=16,
        blockmax_keep=n_blocks, blockmax_block_size=256))
    s1, i1 = svc_bm.search_batch(qs)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(s0, s1, rtol=1e-5, atol=1e-6)


def test_ann_service_stats_mutations_hold_lock(small_corpus):
    """Regression (reprolint rule ``lockdiscipline``): the worker thread
    bumped ``async_launches`` and recorded request latencies off-lock, and
    ``rejected`` / ``reset_latency`` mutated shared stats from caller
    threads off-lock.  Instrument the lock and the mutation points (the
    counters and both latency histograms) and the histograms' reads, then
    drive every path: any off-lock mutation or read is recorded as a
    violation."""
    import queue as queue_mod
    import threading

    violations = []

    class CheckedLock:
        """RLock wrapper that knows whether the current thread holds it."""

        def __init__(self):
            self._lock = threading.RLock()
            self._local = threading.local()

        def __enter__(self):
            self._lock.acquire()
            self._local.depth = getattr(self._local, "depth", 0) + 1
            return self

        def __exit__(self, *exc):
            self._local.depth -= 1
            self._lock.release()

        @property
        def held(self):
            return getattr(self._local, "depth", 0) > 0

    class GuardedHistogram(obs.LatencyHistogram):
        def __init__(self, name, lock):
            super().__init__()
            self._name = name
            self._guard = lock

        def add(self, seconds):
            if not self._guard.held:
                violations.append(f"{self._name}.add")
            super().add(seconds)

        def clear(self):
            if not self._guard.held:
                violations.append(f"{self._name}.clear")
            super().clear()

        def percentile(self, q):
            if not self._guard.held:
                violations.append(f"{self._name}.percentile")
            return super().percentile(q)

    guarded_ints = {"async_launches", "rejected", "batches",
                    "queries_served", "queue_wait_s", "async_requests"}

    class GuardedService(AnnService):
        def __setattr__(self, name, value):
            if name in guarded_ints and getattr(self, "_armed", False) \
                    and not self._lock.held:
                violations.append(name)
            object.__setattr__(self, name, value)

    v = jnp.asarray(small_corpus[:400])
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    svc = GuardedService(idx, cfg, AnnServiceConfig(
        k=5, depth=50, rerank=False, max_batch=4, max_wait_s=0.005,
        queue_depth=8))
    lock = CheckedLock()
    svc._lock = lock
    svc._lat = GuardedHistogram("_lat", lock)
    svc._req_lat = GuardedHistogram("_req_lat", lock)
    svc._armed = True

    svc.search_batch(small_corpus[:8])           # sync path
    svc.start_async()
    futs = [svc.search_async(small_corpus[i]) for i in range(4)]
    for f in futs:
        f.result(timeout=30)                     # worker path
    with svc._lock:                              # back the queue up
        rejected = 0
        for i in range(32):
            try:
                svc.search_async(small_corpus[i % 8])
            except queue_mod.Full:
                rejected += 1                    # rejection path
    svc.stop_async()
    svc.reset_latency()                          # histogram-clear path
    assert rejected >= 1
    assert svc.stats()["rejected"] == rejected
    assert isinstance(svc._req_lat, GuardedHistogram) and svc._req_lat.n == 0
    assert violations == []


SHARDED_SPANS = r"""
import glob, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core.segments import IndexWriter
from repro.core.types import FakeWordsConfig
from repro.serve.ann_service import AnnService, AnnServiceConfig
rng = np.random.default_rng(3)
w = IndexWriter(FakeWordsConfig(), shards=4, merge_policy=None)
jax.profiler.start_trace(sys.argv[1])
w.add(rng.normal(size=(1001, 32)).astype(np.float32))
svc = AnnService(writer=w, service=AnnServiceConfig(k=10, depth=50, max_batch=16))
svc.search_batch(rng.normal(size=(16, 32)).astype(np.float32))
jax.profiler.stop_trace()
(path,) = glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"), recursive=True)
spans = {ev.name: dict(ev.stats) for plane in jax.profiler.ProfileData.from_file(path).planes
         for line in plane.lines for ev in line.events
         if ev.name in ("writer.flush", "packed.pack", "shard.merge")}
stats = svc.stats()
print(json.dumps({"spans": spans, "stats": {k: stats[k] for k in
      ("shards", "shard_live_rows", "merge_bytes_per_launch", "packed_bucket")}}))
"""


def test_sharded_service_stats_and_spans(tmp_path):
    """A doc-sharded writer's service (four host devices, in a subprocess):
    ``stats()`` gives the chips, each chip's live rows and the merge's bytes
    per launch, and the writer's flush, the per-chip pack and the sharded
    dispatch carry ``shards`` and the rows a chip holds."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_SPANS, str(tmp_path)], capture_output=True,
        text=True, timeout=600, env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    spans, stats = out["spans"], out["stats"]
    # 1,001 rows over four chips: 251 + 250 + 250 + 250, in blocks of 251.
    assert spans["writer.flush"] == {"rows": 1001, "segments": 0, "shards": 4, "chip_rows": 251}
    assert spans["packed.pack"] == {"rows": 1001, "segments": 1, "shards": 4, "chip_rows": 251}
    # 16 queries x depth 50 x (4 chips' (score, id) pairs + one rerank score).
    assert spans["shard.merge"] == {"shards": 4, "bytes": 16 * 50 * (8 * 4 + 4)}
    assert stats == {"shards": 4, "shard_live_rows": [251, 250, 250, 250],
                     "merge_bytes_per_launch": 16 * 50 * 36, "packed_bucket": 256}
