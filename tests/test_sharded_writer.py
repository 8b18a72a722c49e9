"""A doc-sharded writer (``IndexWriter(shards=4)``) served through
``AnnService`` returns the one-device writer's answers over the same rows,
and the plain reference's: the global top-``depth`` by match score (classic
fake words, lexical LSH collisions or exact cosine), then the exact rerank.

The sharded cases run in subprocesses with four host devices (like
tests/test_distributed.py), so this process keeps its single device.

Scores are held to 2 ulps of 1.0 (2**-22) against the one-device writer,
not bitwise: the match scores are the same float32 sums over the same
bfloat16 postings, and each rerank score is one float32 dot of a query and
a candidate, taken by the same einsum at the same candidate position; only
XLA's choice of reduction order inside the per-chip program could move a
last bit.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packed as packed_mod
from repro.core.segments import IndexWriter
from repro.core.types import FakeWordsConfig, KdTreeConfig
from repro.serve.ann_service import AnnService, AnnServiceConfig

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

PRELUDE = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [%(root)r, os.path.join(%(root)r, "src")]
import jax, jax.numpy as jnp, numpy as np
import dataclasses
from bench.references import fakewords_classic, lexical_lsh
from repro.core.segments import IndexWriter, TieredMergePolicy
from repro.core.types import BruteForceConfig, FakeWordsConfig, LexicalLshConfig
from repro.serve.ann_service import AnnService, AnnServiceConfig

ULPS = 2.0 ** -22
CFG = FakeWordsConfig(quantization=50)
rng = np.random.default_rng(7)


def pair(chunks, deletes=(), cfg=CFG, **kw):
    \"\"\"A 4-shard and a one-device writer fed the same adds and deletes.\"\"\"
    ws = [IndexWriter(cfg, shards=4, **kw), IndexWriter(cfg, **kw)]
    for w in ws:
        for c in chunks:
            w.add(c)
            w.flush()
        w.delete(np.asarray(deletes, np.int64))
    return ws


def one_shards_rows(w):
    \"\"\"No device buffer of a segment or of the packed view holds more than
    one chip's rows.\"\"\"
    reader = w.refresh()
    pk = reader.packed_segments()
    leaves = [pk.live, pk.ids] + jax.tree.leaves(pk.view)
    for seg in reader.segments:
        leaves += jax.tree.leaves(seg.ann.index)
    rows = {4 * pk.bucket} | {seg.ann.num_docs for seg in reader.segments}
    n = 0
    for x in leaves:
        if x.ndim and x.shape[0] in rows:
            shards = x.addressable_shards
            assert len({s.device for s in shards}) == 4, x.sharding
            assert all(s.data.shape[0] == x.shape[0] // 4 for s in shards), x.sharding
            n += 1
    # The live bitmap, the id map, a packed leaf and a leaf a segment at least.
    assert n >= 3 + len(reader.segments), n


def agree(svc4, svc1, q, k, depth):
    s4, i4 = svc4.search_batch(q)
    s1, i1 = svc1.search_batch(q)
    np.testing.assert_array_equal(i4, i1)
    assert np.max(np.abs(s4 - s1)) <= ULPS, np.max(np.abs(s4 - s1))
    return s4, i4


def plain(w, q, s, ids, depth):
    \"\"\"The plain reference of the writer's encoding over the live rows,
    float32 at the highest matmul precision: every returned id lies in its
    global top-depth (up to its rounding slack), and every score is the
    id's exact cosine.  The rows of each global id are read from the
    one-device writer ``w``'s segments (its normalized originals), since a
    merge after deletes renumbers the ids.\"\"\"
    reader = w.refresh()
    rows = np.concatenate([seg.source_rows() for seg in reader.segments])
    gids = reader.live_global_ids()
    live = jnp.asarray(rows[gids])
    with jax.default_matmul_precision("highest"):
        qn = fakewords_classic.normalize(jnp.asarray(q))
        if isinstance(w.config, BruteForceConfig):
            tol = 1e-5  # float32 cosines summed in another order
            full = np.asarray(qn @ fakewords_classic.normalize(live).T)
        else:
            ref, args = ((fakewords_classic, {"quantization": 50})
                         if isinstance(w.config, FakeWordsConfig)
                         else (lexical_lsh, dataclasses.asdict(w.config)))
            state, tol = ref.prepare(live, args, 4096), ref.SCORE_TOL
            full = np.asarray(ref.scores(ref.encode_queries(state, qn), ref.encode_docs(state, live)))
    col = {int(g): j for j, g in enumerate(gids)}
    for b in range(len(q)):
        top = np.sort(full[b])[::-1]
        dth, scale = top[min(depth, len(top)) - 1], max(abs(top[0]), 1e-30)
        got = full[b, [col[int(g)] for g in ids[b]]]
        assert np.all(got >= dth - tol * scale), (b, got.min(), dth)
        cos = rows[ids[b]] @ q[b] / (np.linalg.norm(rows[ids[b]], axis=1) * np.linalg.norm(q[b]))
        np.testing.assert_allclose(s[b], cos, atol=1e-5)
"""


def run(body: str):
    code = PRELUDE % {"root": ROOT} + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


CASES = {
    # 2,000 rows in one flush: 500 a chip.
    "one_flush": """
    rows = rng.normal(size=(2000, 32)).astype(np.float32)
    w4, w1 = pair([rows])
    """,
    # Ragged flushes (none a multiple of 4), and merges of them.
    "ragged_flushes": """
    rows = rng.normal(size=(1999, 32)).astype(np.float32)
    w4, w1 = pair(np.array_split(rows, [301, 602, 1115, 1450, 1451]),
                  merge_policy=TieredMergePolicy(merge_factor=2, floor_docs=400))
    assert w4.num_segments == w1.num_segments < 6, (w4.num_segments, w1.num_segments)
    """,
    # Deletes across segments and in the buffer, then a refresh.
    "deletes_refresh": """
    rows = rng.normal(size=(1203, 32)).astype(np.float32)
    w4, w1 = pair([rows[:601], rows[601:1201]], deletes=rng.choice(1201, 150, replace=False),
                  merge_policy=None)
    for w in (w4, w1):
        w.add(rows[1201:])
        w.delete([1201])
    """,
    # Lexical LSH and brute force: ragged flushes, deletes across segments
    # and a merge.  LSH zero pad rows carry signatures that collide with
    # the queries' "0.0" tokens; only the live bitmap keeps them out.
    "lsh_ragged_deletes": """
    rows = rng.normal(size=(1203, 32)).astype(np.float32)
    w4, w1 = pair(np.array_split(rows, [301, 602, 1115]), deletes=rng.choice(1203, 150, replace=False),
                  cfg=LexicalLshConfig(),
                  merge_policy=TieredMergePolicy(merge_factor=2, floor_docs=400))
    """,
    "bruteforce_ragged_deletes": """
    rows = rng.normal(size=(1203, 32)).astype(np.float32)
    w4, w1 = pair(np.array_split(rows, [301, 602, 1115]), deletes=rng.choice(1203, 150, replace=False),
                  cfg=BruteForceConfig(),
                  merge_policy=TieredMergePolicy(merge_factor=2, floor_docs=400))
    """,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sharded_writer_matches_one_device_and_reference(case):
    run(CASES[case] + """
    k, depth = 10, 50
    scfg = AnnServiceConfig(k=k, depth=depth, rerank=True, max_batch=16)
    svc4, svc1 = AnnService(writer=w4, service=scfg), AnnService(writer=w1, service=scfg)
    extra = rng.normal(size=(5, 32)).astype(np.float32)
    for w in (w4, w1):
        w.add(extra)
    svc4.refresh(); svc1.refresh()
    q = rng.normal(size=(16, 32)).astype(np.float32)
    s, ids = agree(svc4, svc1, q, k, depth)
    live = set(w1.refresh().live_global_ids().tolist())
    assert set(ids.reshape(-1).tolist()) <= live
    plain(w1, q, s, ids, depth)
    one_shards_rows(w4)
    print("ok")
    """)


def test_sharded_async_path_and_kernel():
    """Single queries through the micro-batcher return ``search_batch``'s
    answers, the fused kernel (interpret mode) inside the per-chip program
    agrees with the one-device writer, and a refresh after a delete
    recompiles nothing."""
    run("""
    rows = rng.normal(size=(603, 32)).astype(np.float32)
    w4, w1 = pair([rows[:300], rows[300:]], deletes=[5, 400])
    scfg = AnnServiceConfig(k=10, depth=40, rerank=True, max_batch=8, use_kernel=True)
    svc4, svc1 = AnnService(writer=w4, service=scfg), AnnService(writer=w1, service=scfg)
    q = rng.normal(size=(8, 32)).astype(np.float32)
    s, ids = agree(svc4, svc1, q, 10, 40)
    svc4.start_async()
    futs = [svc4.search_async(x) for x in q]
    got = [f.result(timeout=600) for f in futs]
    svc4.stop_async()
    np.testing.assert_array_equal(np.concatenate([g[1] for g in got]), ids)
    np.testing.assert_array_equal(np.concatenate([g[0] for g in got]), s)
    assert svc4.stats()["async_launches"] >= 1
    # An NRT refresh that keeps the bucket and the segments compiles
    # neither the per-chip pack nor the search again.
    from bench.lib.compiles import CompileCounter
    counter = CompileCounter().install()
    w4.delete([7])  # the first refresh warms the eager statistics ops
    svc4.refresh()
    svc4.search_batch(q)
    before = counter.compiles
    w4.delete([8])
    svc4.refresh()
    assert 8 not in svc4.search_batch(q)[1]
    assert counter.compiles == before, counter.compiles - before
    for call in (lambda: w4.commit("unused"), lambda: w4.add(q, metadata={"f": np.zeros(8)})):
        try:
            call()
            raise AssertionError("a sharded writer took a one-device-only call")
        except ValueError as e:
            assert "one-device only" in str(e), e
    print("ok")
    """)


def test_monolithic_mesh_search_returns_one_device_answers():
    """``AnnIndex.build(mesh=)`` served with ``AnnService(mesh=)`` merges
    through the same global top-depth: the one-device ids, with and without
    the rerank."""
    run("""
    from repro.core import distributed
    from repro.core.index import AnnIndex
    rows = rng.normal(size=(1024, 32)).astype(np.float32)
    q = rng.normal(size=(16, 32)).astype(np.float32)
    mesh = distributed.make_mesh((4,), ("data",))
    ann = AnnIndex.build(jnp.asarray(rows), CFG, mesh=mesh, shard_axes=("data",))
    one = AnnIndex.build(jnp.asarray(rows), CFG)
    for rerank in (False, True):
        scfg = AnnServiceConfig(k=10, depth=50, rerank=rerank, max_batch=16)
        s4, i4 = AnnService(ann, scfg, mesh=mesh, shard_axes=("data",)).search_batch(q)
        s1, i1 = AnnService(one, scfg).search_batch(q)
        np.testing.assert_array_equal(i4, i1)
        assert np.max(np.abs(s4 - s1)) <= ULPS
    print("ok")
    """)


def test_sharded_writer_guard_rails():
    with pytest.raises(ValueError, match="shards must be >= 1"):
        IndexWriter(FakeWordsConfig(), shards=0)
    with pytest.raises(ValueError, match="global_stats=True"):
        IndexWriter(KdTreeConfig(backend="scan"), shards=2)
    with pytest.raises(ValueError, match="global_stats=True"):
        IndexWriter(FakeWordsConfig(), shards=2, global_stats=False)
    with pytest.raises(ValueError, match="local devices"):
        IndexWriter(FakeWordsConfig(), shards=2)  # one device in this process
    with pytest.raises(ValueError, match="one-device only"):
        IndexWriter.open("unused", shards=2)


def test_one_device_writer_is_todays_path(rng):
    """``shards`` unset: no mesh, the one-device packed view and executable,
    and the answers of the per-segment reference loop."""
    rows = rng.normal(size=(700, 32)).astype(np.float32)
    ws = [IndexWriter(FakeWordsConfig(), merge_policy=None),
          IndexWriter(FakeWordsConfig(), merge_policy=None, shards=1)]
    scfg = AnnServiceConfig(k=10, depth=50, rerank=True, max_batch=16)
    q = rng.normal(size=(16, 32)).astype(np.float32)
    out = []
    for w in ws:
        w.add(rows[:400])
        w.flush()
        w.add(rows[400:])
        w.delete([3, 450])
        svc = AnnService(writer=w, service=scfg)
        packed_mod.EXEC_CACHE.clear()
        out.append(svc.search_batch(q))
        reader = svc.ann
        assert reader.mesh is None and reader.shards == 1
        pk = reader.packed_segments()
        assert pk.ids is None and pk.shards == 1 and pk.bucket == 768
        (exe,) = packed_mod.EXEC_CACHE.executables("search")
        assert exe.as_text().startswith("HloModule jit_packed_search,")
        s_loop, i_loop = reader.search(jnp.asarray(q), k=10, depth=50, rerank=True,
                                       packed=False)
        np.testing.assert_array_equal(out[-1][1], np.asarray(i_loop))
        stats = svc.stats()
        assert stats["shards"] == 1 and stats["merge_bytes_per_launch"] == 0
        assert stats["shard_live_rows"] == [698]
    np.testing.assert_array_equal(out[0][1], out[1][1])
    np.testing.assert_array_equal(out[0][0], out[1][0])
