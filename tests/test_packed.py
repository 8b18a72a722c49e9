"""Packed single-launch segmented search (core/packed.py, docs/DESIGN.md
§14): the packed superbuffer path returns EXACTLY the per-segment loop's
results — ids equal, scores allclose — across segment counts, encodings,
and filters, while the shape-bucketed executable cache keeps recompiles
bounded across refresh cycles.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce
from repro.core import packed as packed_mod
from repro.core.segments import IndexWriter
from repro.core.types import FakeWordsConfig, KdTreeConfig, LexicalLshConfig
from tools.reprolint.trace_audit import assert_max_traces

# The encodings the ISSUE's parity matrix names: classic fp32 postings,
# dot-mode int8 postings, int4 quantized-classic postings, LSH signatures.
MATRIX = [
    ("classic", FakeWordsConfig(quantization=50), "fp32", "exact"),
    ("dot-int8", FakeWordsConfig(quantization=50, scoring="dot"), "int8", "int8"),
    ("int4", FakeWordsConfig(quantization=50), "int4", "exact"),
    ("lsh", LexicalLshConfig(buckets=64, hashes=2), "fp32", "exact"),
]


def _writer(cfg, postings, store, n_segments, rng, dim=32, seg_docs=40):
    w = IndexWriter(
        cfg, rerank_store=store, primary_postings=postings,
        merge_policy=None, use_kernel=False,
    )
    for _ in range(n_segments):
        w.add(rng.normal(size=(seg_docs, dim)).astype(np.float32))
        w.flush()
    return w


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


@pytest.mark.parametrize("n_segments", [1, 4, 16])
@pytest.mark.parametrize(
    "name,cfg,postings,store", MATRIX, ids=[m[0] for m in MATRIX]
)
def test_packed_parity(name, cfg, postings, store, n_segments, rng):
    """Packed single-launch == per-segment loop: exact ids, allclose
    scores, rerank on and off — unfiltered AND under deletes ∧ predicate."""
    w = _writer(cfg, postings, store, n_segments, rng)
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(6, 32)).astype(np.float32))
    _assert_packed_equals_loop(reader, queries)

    # Deletes ∧ predicate: drop 10% of docs, keep a random 70% predicate.
    n = reader.max_doc
    w.delete(rng.choice(n, size=max(1, n // 10), replace=False))
    reader = w.refresh()
    fm = jnp.asarray(rng.random(n) < 0.7)
    _assert_packed_equals_loop(reader, queries, fm=fm)


def test_packed_parity_per_query_filter(rng):
    """(B, max_doc) per-query predicate bitmaps ride the packed path too."""
    w = _writer(FakeWordsConfig(quantization=50), "fp32", "exact", 4, rng)
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    fm = jnp.asarray(rng.random((5, reader.max_doc)) < 0.6)
    _assert_packed_equals_loop(reader, queries, fm=fm)


def test_packed_kdtree_scan_parity(rng):
    """The kd-scan encoding (global-stats refit) packs and matches too."""
    w = _writer(
        KdTreeConfig(dims=8, backend="scan"), "fp32", "exact", 4, rng
    )
    reader = w.refresh()
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    _assert_packed_equals_loop(reader, queries)


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


def test_donated_incremental_append(rng):
    """Append-only refreshes of a stats-static encoding absorb the prior
    snapshot's packed buffers in place instead of re-concatenating."""
    cfg = LexicalLshConfig(buckets=64, hashes=2)
    # 600 docs -> bucket 768, and 620 stays in the same rung with room
    # for the 128-row append block.
    w = _writer(cfg, "fp32", "exact", 1, rng, seg_docs=600)
    r0 = w.refresh()
    assert r0.packed_segments().appends == 0
    w.add(rng.normal(size=(20, 32)).astype(np.float32))
    w.flush()
    r1 = w.refresh()
    pk = r1.packed_segments()
    assert pk.appends == 1  # donated dynamic_update_slice, not a repack
    queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
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


def test_packed_blockmax_exact_at_full_keep(rng):
    """blockmax_keep = every block is a pure reshuffle of the exact scan:
    segmented blockmax (over the packed view) == the unpruned loop."""
    for cfg in (
        FakeWordsConfig(quantization=50),
        LexicalLshConfig(buckets=64, hashes=2),
    ):
        w = _writer(cfg, "fp32", "exact", 4, rng, seg_docs=40)
        reader = w.refresh()
        queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
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


def test_packed_sharded_composition(rng):
    """make_packed_segmented_search: pack -> doc-shard -> pod fan-out with
    the live∧predicate bitmap sharded with the rows (subprocess with 8
    fake host devices, like tests/test_distributed.py)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bruteforce, distributed
        from repro.core.segments import IndexWriter
        from repro.core.types import FakeWordsConfig

        rng = np.random.default_rng(0)
        w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None,
                        use_kernel=False)
        w.add(rng.normal(size=(300, 32)).astype(np.float32)); w.flush()
        w.add(rng.normal(size=(212, 32)).astype(np.float32)); w.flush()
        w.delete(rng.choice(512, size=40, replace=False))
        reader = w.refresh()  # 512 rows -> bucket 512: divisible by 4
        queries = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
        mesh = jax.make_mesh((4,), ("data",))
        fn, idx_sh, filt_sh = distributed.make_packed_segmented_search(
            mesh, reader, ("data",), k=10, depth=50, rerank=True,
            use_kernel=False)
        q_rep = reader.encode_queries(queries)
        s_sh, i_sh = fn(idx_sh, q_rep, bruteforce.l2_normalize(queries),
                        filt_sh)
        s_1, i_1 = reader.search(queries, k=10, depth=50, rerank=True,
                                 packed=False)
        # Rerank fp rounding differs per shard partition; like the other
        # sharded suites, assert set overlap + score closeness, not
        # bitwise id order.
        from repro.core import eval as ev
        ov = float(ev.overlap(i_1, i_sh))
        assert ov >= 0.95, ov
        np.testing.assert_allclose(np.asarray(s_1)[:, :8],
                                   np.asarray(s_sh)[:, :8],
                                   rtol=1e-4, atol=1e-5)
        print("packed sharded ok", ov)
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, PYTHONPATH=src),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
