"""Packed single-launch segmented search (core/packed.py, docs/DESIGN.md
§14): the packed superbuffer path returns EXACTLY the per-segment loop's
results — ids equal, scores allclose — across segment counts, encodings,
and filters, while the shape-bucketed executable cache keeps recompiles
bounded across refresh cycles.
"""
import dataclasses
import glob
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce
from repro.core import packed as packed_mod
from repro.core.segments import IndexWriter
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
)
from tools.reprolint.trace_audit import assert_max_traces

CLASSIC = FakeWordsConfig(quantization=50)
DOT = FakeWordsConfig(quantization=50, scoring="dot")
LSH = LexicalLshConfig(buckets=64, hashes=2)
LSH_300 = LexicalLshConfig(buckets=150, hashes=2)

# The parity matrix: classic fp32 postings, dot-mode int8 postings, int4
# quantized-classic postings, LSH signatures at 32-d; then the served
# widths, which the packed view stores lane-aligned (classic T = 400 ->
# 512, LSH S = 300 -> 384, 200-d vectors -> 256), on the XLA path and on
# the kernel (interpret mode off a TPU).
MATRIX = [
    ("classic", CLASSIC, "fp32", "exact", 32, False),
    ("dot-int8", DOT, "int8", "int8", 32, False),
    ("int4", CLASSIC, "int4", "exact", 32, False),
    ("lsh", LSH, "fp32", "exact", 32, False),
    ("classic-t400", CLASSIC, "fp32", "exact", 200, False),
    ("classic-t400-kernel", CLASSIC, "fp32", "exact", 200, True),
    ("lsh-s300", LSH_300, "fp32", "exact", 200, False),
    ("lsh-s300-kernel", LSH_300, "fp32", "exact", 200, True),
    ("bruteforce-d200", BruteForceConfig(), "fp32", "exact", 200, False),
    ("bruteforce-d200-kernel", BruteForceConfig(), "fp32", "exact", 200, True),
]


def _writer(cfg, postings, store, n_segments, rng, dim=32, seg_docs=40,
            use_kernel=False):
    w = IndexWriter(
        cfg, rerank_store=store, primary_postings=postings,
        merge_policy=None, use_kernel=use_kernel,
    )
    for _ in range(n_segments):
        w.add(rng.normal(size=(seg_docs, dim)).astype(np.float32))
        w.flush()
    return w


def _unaligned(reader):
    """The reader's packed snapshot with every lane-aligned leaf cut back
    to its stat view's (logical) width: the reference layout."""
    pk = reader.packed_segments()
    v0 = reader._ensure_views()[0][0]
    cut = {}
    for path in packed_mod._doc_leaf_paths(reader.config, pk.view):
        leaf = packed_mod._get_path(pk.view, path)
        ref = packed_mod._get_path(v0, path)
        # A stat view may leave the kd lift [d; -|d|^2] to the pack.
        width = v0.reduced.shape[1] + 1 if ref is None else ref.shape[-1]
        if leaf.ndim == 2 and leaf.shape[1] != width:
            cut[path] = leaf[:, :width]
    assert cut, "nothing is stored lane-aligned"
    view = packed_mod._replace_paths(pk.view, cut)
    return dataclasses.replace(pk, view=view, bm_cache={})


def _assert_layout_bitwise(reader, queries, fm=None, k=10, depth=50,
                           rerank=False, n_keep=None, block=64):
    """The lane-aligned packed view answers bitwise like the same snapshot
    at its logical widths: ids and scores, kernel or XLA path."""
    q = bruteforce.l2_normalize(queries)
    out = []
    for pk in (reader.packed_segments(), _unaligned(reader)):
        bm = None if n_keep is None else packed_mod.packed_blockmax(
            pk, reader.config, block)
        out.append(packed_mod.packed_search(
            pk, reader.pipeline, reader._packed_matcher(), q, k, depth,
            rerank, reader.quantized_rerank, reader.use_kernel, fm=fm,
            n_keep=n_keep, bm=bm,
        ))
    (s0, i0), (s1, i1) = out
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def _assert_packed_equals_loop(reader, queries, fm=None, k=10, depth=50):
    for rerank in (False, True):
        s0, i0 = reader.search(
            queries, k=k, depth=depth, rerank=rerank, packed=False,
            filter_mask=fm,
        )
        s1, i1 = reader.search(
            queries, k=k, depth=depth, rerank=rerank, packed=True,
            filter_mask=fm,
        )
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(
            np.asarray(s0), np.asarray(s1), rtol=1e-5, atol=1e-6
        )
        _assert_layout_bitwise(reader, queries, fm, k, depth, rerank)


def _delete_tenth(w, reader, rng):
    """Delete 10% of the docs and return the new reader."""
    n = reader.max_doc
    w.delete(rng.choice(n, size=max(1, n // 10), replace=False))
    return w.refresh()


@pytest.mark.parametrize("n_segments", [1, 4, 16])
@pytest.mark.parametrize(
    "name,cfg,postings,store,dim,use_kernel", MATRIX,
    ids=[m[0] for m in MATRIX],
)
def test_packed_parity(
    name, cfg, postings, store, dim, use_kernel, n_segments, rng
):
    """Packed single-launch == per-segment loop: exact ids, allclose
    scores, rerank on and off — unfiltered AND under deletes ∧ predicate,
    over a bucket-padded tail; and bitwise what the same snapshot answers
    at its logical widths."""
    w = _writer(cfg, postings, store, n_segments, rng, dim=dim,
                use_kernel=use_kernel)
    reader = w.refresh()
    assert reader.packed_segments().n_rows < reader.packed_segments().bucket
    queries = jnp.asarray(rng.normal(size=(6, dim)).astype(np.float32))
    _assert_packed_equals_loop(reader, queries)

    # Deletes ∧ predicate: drop 10% of docs, keep a random 70% predicate.
    reader = _delete_tenth(w, reader, rng)
    fm = jnp.asarray(rng.random(reader.max_doc) < 0.7)
    _assert_packed_equals_loop(reader, queries, fm=fm)


def test_packed_parity_per_query_filter(rng):
    """(B, max_doc) per-query predicate bitmaps ride the packed path too."""
    w = _writer(FakeWordsConfig(quantization=50), "fp32", "exact", 4, rng)
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    fm = jnp.asarray(rng.random((5, reader.max_doc)) < 0.6)
    _assert_packed_equals_loop(reader, queries, fm=fm)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_packed_kdtree_scan_parity(use_kernel, rng):
    """The kd-scan encoding (global-stats refit) packs and matches too: the
    9-wide lift is stored 128 wide, over a padded tail and deletes."""
    w = _writer(
        KdTreeConfig(dims=8, backend="scan"), "fp32", "exact", 4, rng,
        use_kernel=use_kernel,
    )
    reader = w.refresh()
    pk = reader.packed_segments()
    assert pk.view.lifted.shape == (pk.bucket, 128) and pk.n_rows < pk.bucket
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    _assert_packed_equals_loop(reader, queries)
    _assert_packed_equals_loop(_delete_tenth(w, reader, rng), queries)


def test_bucket_ladder():
    assert packed_mod.bucket_rows(1) == 256
    assert packed_mod.bucket_rows(256) == 256
    assert packed_mod.bucket_rows(257) == 384
    assert packed_mod.bucket_rows(600) == 768
    assert packed_mod.bucket_rows(769) == 1024
    assert packed_mod.bucket_rows(1025) == 1536
    # ladder overhead never exceeds 50% (geometric with 1.5x midpoints)
    for n in range(1, 5000, 37):
        b = packed_mod.bucket_rows(n)
        assert n <= b <= max(256, int(n * 1.5))


def test_recompile_guard(rng):
    """≤ 1 search compile per (bucket, encoding) across 10 NRT refresh
    cycles — asserted on ACTUAL backend-compile events via the trace
    audit, not the executable cache's own bookkeeping (which cannot see
    retraces that bypass it)."""
    cache = packed_mod.EXEC_CACHE
    cache.clear()
    cfg = LexicalLshConfig(buckets=64, hashes=2)
    # 560 docs -> bucket 768 with room for all nine 8-row appends in the
    # preferred 128-row block rung (no rung narrowing inside this test —
    # that edge has its own test below).
    w = _writer(cfg, "fp32", "exact", 1, rng, seg_docs=560)
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))

    def cycle(i):
        if i:
            w.add(rng.normal(size=(8, 32)).astype(np.float32))
            w.flush()
        reader = w.refresh()
        reader.search(queries, k=10, depth=50, packed=True)
        assert reader.packed_segments().bucket == 768

    # Cycle 0 compiles the search executable; cycle 1 adds the donated
    # append executable.  Everything after must reuse both.
    cycle(0)
    cycle(1)
    with assert_max_traces(0, "steady-state NRT cycles inside one bucket"):
        for i in range(2, 10):
            cycle(i)
    assert cache.hits >= 8, cache.stats()


def test_append_rung_narrowing(rng):
    """Near the top of a bucket the donated append narrows its block rung
    (128 -> 64 -> ...) instead of falling back to full repacks — which
    would recompile a growing-arity concatenate on EVERY later refresh.
    The narrower rung costs one compile burst; after that, steady state is
    compile-free again."""
    cfg = LexicalLshConfig(buckets=64, hashes=2)
    # 700 docs -> bucket 768: only 68 rows of room, so the preferred
    # 128-row rung never fits and appends must narrow (64, then 32).
    w = _writer(cfg, "fp32", "exact", 1, rng, seg_docs=700)
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))

    def cycle():
        w.add(rng.normal(size=(8, 32)).astype(np.float32))
        w.flush()
        reader = w.refresh()
        reader.search(queries, k=10, depth=50, packed=True)
        return reader.packed_segments()

    w.refresh().search(queries, k=10, depth=50, packed=True)  # warm search
    for _ in range(3):  # rungs 64, 32, 32(hit)
        pk = cycle()
    assert pk.bucket == 768
    assert pk.appends == 3, "appends near the bucket edge must absorb"
    with assert_max_traces(0, "warmed narrow rung must be a cache hit"):
        pk = cycle()
    assert pk.appends == 4


@pytest.mark.parametrize(
    "cfg,dim,use_kernel",
    [(LSH, 32, False), (LSH_300, 200, False), (LSH_300, 200, True)],
    ids=["s128-xla", "s300-xla", "s300-kernel"],
)
def test_donated_incremental_append(cfg, dim, use_kernel, rng):
    """Append-only refreshes of a stats-static encoding absorb the prior
    snapshot's packed buffers in place instead of re-concatenating; the
    appended block is stored lane-aligned like the rest, and deletes in the
    old rows ride along."""
    # 600 docs -> bucket 768, and 620 stays in the same rung with room
    # for the 128-row append block.
    w = _writer(cfg, "fp32", "exact", 1, rng, dim=dim, seg_docs=600,
                use_kernel=use_kernel)
    r0 = w.refresh()
    assert r0.packed_segments().appends == 0
    w.delete(rng.choice(600, size=60, replace=False))
    w.add(rng.normal(size=(20, dim)).astype(np.float32))
    w.flush()
    r1 = w.refresh()
    pk = r1.packed_segments()
    assert pk.appends == 1  # donated dynamic_update_slice, not a repack
    assert pk.view.sig.shape[1] % 128 == 0
    queries = jnp.asarray(rng.normal(size=(4, dim)).astype(np.float32))
    _assert_packed_equals_loop(r1, queries)
    # The donation neutered the old reader's pack; it lazily repacks.
    assert r0._packed is None
    r0_again = r0.packed_segments()
    assert r0_again is not None and r0_again.appends == 0
    _assert_packed_equals_loop(r0, queries)


def test_packed_executables_have_stable_names(rng):
    """The packed search and the donated append compile as modules named
    ``jit_packed_search`` and ``jit_packed_append``, so a profiler trace
    names them the same after any refactor of their bodies."""
    cfg = LexicalLshConfig(buckets=64, hashes=2)
    w = _writer(cfg, "fp32", "exact", 1, rng, seg_docs=600)
    r0 = w.refresh()
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    r0.search(queries, k=5, depth=20, packed=True)
    w.add(rng.normal(size=(20, 32)).astype(np.float32))
    r1 = w.refresh()
    assert r1.packed_segments().appends == 1
    for kind in ("search", "append"):
        exes = packed_mod.EXEC_CACHE.executables(kind)
        assert exes
        for exe in exes:
            assert exe.as_text().startswith(f"HloModule jit_packed_{kind},")


def test_classic_repacks_fully_and_stays_exact(rng):
    """Classic scoring rebuilds per-row state under new global idf, so a
    refresh must NOT incrementally append — and stays loop-exact."""
    cfg = FakeWordsConfig(quantization=50)
    w = _writer(cfg, "fp32", "exact", 2, rng)
    r0 = w.refresh()
    r0.packed_segments()
    w.add(rng.normal(size=(30, 32)).astype(np.float32))
    w.flush()
    r1 = w.refresh()
    pk = r1.packed_segments()
    assert pk.appends == 0
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    _assert_packed_equals_loop(r1, queries)


def test_packed_false_forces_loop_and_env_kill_switch(rng, monkeypatch):
    """packed=False serves the reference loop; REPRO_PACKED=0 flips the
    process default (checked via the module flag, set at import)."""
    w = _writer(FakeWordsConfig(quantization=50), "fp32", "exact", 2, rng)
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(3, 32)).astype(np.float32))
    reader.search(queries, packed=False)
    assert reader._packed is None  # the loop never built the superbuffer
    reader.search(queries, packed=True)
    assert reader._packed is not None


@pytest.mark.parametrize(
    "cfg,dim,use_kernel",
    [
        (CLASSIC, 32, False), (LSH, 32, False),
        (CLASSIC, 200, False), (CLASSIC, 200, True),
        (LSH_300, 200, False), (LSH_300, 200, True),
        (DOT, 32, False), (DOT, 200, True),
    ],
    ids=["classic-t64-xla", "lsh-s128-xla", "classic-t400-xla",
         "classic-t400-kernel", "lsh-s300-xla", "lsh-s300-kernel",
         "dot-t64-xla", "dot-t400-kernel"],
)
def test_packed_blockmax_exact_at_full_keep(cfg, dim, use_kernel, rng):
    """blockmax_keep = every block is a pure reshuffle of the exact scan:
    segmented blockmax (over the lane-aligned packed view) == the unpruned
    loop, before and after deletes."""
    w = _writer(cfg, "fp32", "exact", 4, rng, dim=dim, seg_docs=40,
                use_kernel=use_kernel)
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(4, dim)).astype(np.float32))
    for reader in (reader, _delete_tenth(w, reader, rng)):
        s0, i0 = reader.search(queries, k=10, depth=50, packed=False)
        pk = reader.packed_segments()
        keep = pk.bucket // 64  # block_size=64 -> keep ALL blocks
        s1, i1 = reader.search(
            queries, k=10, depth=50, packed=True,
            blockmax_keep=keep, blockmax_block_size=64,
        )
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(
            np.asarray(s0), np.asarray(s1), rtol=1e-5, atol=1e-6
        )
        _assert_layout_bitwise(reader, queries, n_keep=keep)
        # Keeping 3 of 16-row blocks: the bounds over the aligned view keep
        # the blocks the logical-width bounds keep.
        _assert_layout_bitwise(reader, queries, n_keep=3, block=16)


def test_packed_static_rows_bound(rng):
    """static_rows=True masks pad rows through the kernels' static n_docs
    bound instead of a bitmap — same results (shape-static callers)."""
    w = _writer(LexicalLshConfig(buckets=64, hashes=2), "fp32", "exact",
                2, rng, seg_docs=150)  # 300 rows, bucket 384: padded tail
    reader = w.refresh()
    pk = reader.packed_segments()
    assert pk.n_rows < pk.bucket and not pk.any_deleted
    q = bruteforce.l2_normalize(
        jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32)))
    s1, i1 = packed_mod.packed_search(
        pk, reader.pipeline, reader._packed_matcher(), q,
        k=10, depth=50, rerank=False, quantized=False, use_kernel=False,
        static_rows=True,
    )
    s0, i0 = reader.search(q, k=10, depth=50, packed=False)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-5)


def test_packed_unsupported_falls_back_and_true_raises(rng):
    """global_stats=False (per-segment statistics) cannot pack for
    fake-words: the default silently serves the loop, packed=True raises
    with the reason."""
    cfg = FakeWordsConfig(quantization=50)
    w = IndexWriter(cfg, merge_policy=None, use_kernel=False,
                    global_stats=False)
    w.add(np.random.default_rng(1).normal(size=(80, 32)).astype(np.float32))
    w.flush()
    w.add(np.random.default_rng(2).normal(size=(60, 32)).astype(np.float32))
    w.flush()
    reader = w.refresh()
    queries = jnp.asarray(
        np.random.default_rng(3).normal(size=(3, 32)).astype(np.float32))
    s, i = reader.search(queries, k=5, depth=20)  # default: falls back
    assert reader.packed_segments() is None and reader._packed_err
    with pytest.raises(ValueError, match="packed single-launch"):
        reader.search(queries, k=5, depth=20, packed=True)


def test_packed_sharded_composition():
    """The packed single launch over a doc-sharded writer's snapshot (four
    host devices, in a subprocess, like tests/test_distributed.py) takes
    what the one-device packed path takes: two flushes, deletes, a
    (max_doc,) and a (B, max_doc) predicate composed with the live bitmap,
    the rerank, the int8 rerank store and int8 postings.  Ids are the
    one-device writer's; scores agree within 2**-22 (the bound of
    tests/test_sharded_writer.py, which states its reason)."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax.numpy as jnp, numpy as np
        from repro.core.segments import IndexWriter
        from repro.core.types import FakeWordsConfig

        rng = np.random.default_rng(0)
        rows = rng.normal(size=(512, 32)).astype(np.float32)
        dels = rng.choice(512, size=40, replace=False)
        q = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
        pred = rng.random(512) < 0.5
        pred2 = rng.random((4, 512)) < 0.3
        for kw in ({}, {"rerank_store": "int8"}, {"primary_postings": "int8"}):
            readers = []
            for shards in (4, 1):
                w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None,
                                shards=shards, **kw)
                w.add(rows[:300]); w.flush()
                w.add(rows[300:]); w.flush()
                w.delete(dels)
                readers.append(w.refresh())
            assert readers[0].shards == 4 and readers[0].packed_segments().ids is not None
            for fm in (None, pred, pred2):
                for rerank in (False, True):
                    (s4, i4), (s1, i1) = [
                        r.search(q, k=10, depth=50, rerank=rerank, filter_mask=fm)
                        for r in readers
                    ]
                    i4, s4, s1 = np.asarray(i4), np.asarray(s4), np.asarray(s1)
                    np.testing.assert_array_equal(i4, np.asarray(i1))
                    np.testing.assert_array_equal(np.isfinite(s4), np.isfinite(s1))
                    fin = np.isfinite(s1)
                    assert np.max(np.abs(s4[fin] - s1[fin])) <= 2.0 ** -22, (kw, rerank)
                    assert not set(i4[i4 >= 0].tolist()) & set(dels.tolist())
                    if fm is not None:
                        keep = np.broadcast_to(fm, (4, 512))
                        assert all(keep[b, i] for b in range(4) for i in i4[b] if i >= 0)
        print("packed sharded ok")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"


def test_search_temp_bytes_on_compile_span_and_in_stats(rng, tmp_path):
    """Each compile records the executable's temp bytes on its
    ``packed.compile`` span; ``AnnService.stats()`` reports the search
    executable's beside ``packed_bucket``."""
    from repro.serve.ann_service import AnnService, AnnServiceConfig

    w = _writer(LSH_300, "fp32", "exact", 1, rng, dim=200, seg_docs=200)
    svc = AnnService(writer=w, service=AnnServiceConfig(
        k=10, depth=50, rerank=True, max_batch=8))
    assert svc.stats()["packed_bucket"] is None  # nothing packed yet
    packed_mod.EXEC_CACHE.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.search_batch(rng.normal(size=(8, 200)).astype(np.float32))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans = [
        dict(ev.stats)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name == "packed.compile"
    ]
    (search,) = [sp for sp in spans if sp["kind"] == "search"]
    (exe,) = packed_mod.EXEC_CACHE.executables("search")
    temp = exe.memory_analysis().temp_size_in_bytes
    assert search["temp_bytes"] == temp
    stats = svc.stats()
    assert stats["packed_bucket"] == 256
    assert stats["packed_search_temp_bytes"] == temp
