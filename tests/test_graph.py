"""Graph (hnsw) encoding coverage: deterministic build, jit-stable batched
beam search, filtered traversal (masked nodes traversable, never emitted),
segmented-vs-monolithic recall parity through deletes and merge, save/load,
and sharded-build parity (subprocess, 8 fake devices).

The search loop is a fixed-iteration ``fori_loop`` with static ef/beam, so
one compilation serves every same-shape query batch — asserted against the
pipeline jit cache directly.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bruteforce, eval as ev, graph
from repro.core import pipeline as pl
from repro.core.index import AnnIndex
from repro.core.segments import IndexWriter
from repro.core.types import BruteForceConfig, GraphConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Operating point for the filtered-parity test: at N=2000 / 10% selectivity
# the traversal list must hold enough masked-but-traversable nodes to reach
# every filtered neighborhood (docs/DESIGN.md §15); ef=320/beam=16 keeps
# recall within 0.01 of filtered brute force.
WIDE = GraphConfig(ef=320, beam=16)


def _corpus(n=2000, dim=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    x += 0.5 * rng.normal(size=(1, dim)).astype(np.float32)
    return x


def test_graph_build_deterministic(small_corpus):
    """Same rows -> bitwise-identical adjacency and entry points: the build
    has no RNG (exact kNN pools + deterministic prune + sort-based reverse
    fill), so two builds must agree exactly."""
    v = bruteforce.l2_normalize(jnp.asarray(small_corpus))
    cfg = GraphConfig()
    nb1, e1 = graph.build_graph(v, cfg)
    nb2, e2 = graph.build_graph(v, cfg)
    np.testing.assert_array_equal(np.asarray(nb1), np.asarray(nb2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    nb = np.asarray(nb1)
    assert nb.shape == (v.shape[0], cfg.total_degree)
    assert nb.dtype == np.int32
    # no self-loops, ids in range (or -1 padding)
    assert ((nb >= -1) & (nb < v.shape[0])).all()
    assert (nb != np.arange(v.shape[0])[:, None]).all()


def test_graph_search_recall_and_jit_stability(small_corpus):
    """Batched beam search hits high recall at modest ef, and repeated
    same-shape query batches reuse ONE compiled executable (static
    ef/beam/iters + fixed-width loop state -> no retrace)."""
    v = jnp.asarray(small_corpus)
    ann = AnnIndex.build(v, GraphConfig(ef=128, beam=8))
    q = jnp.asarray(small_corpus[:32] + 0.01)
    _, gt_i = bruteforce.exact_topk(v, q, 10, use_kernel=False)
    s, i = ann.search(q, k=10, depth=10)
    assert float(ev.recall_at(gt_i, i)) >= 0.95
    # warm, then assert the pipeline jit cache stops growing
    ann.search(q, k=10, depth=10)
    size = pl._pipeline_search._cache_size()
    for _ in range(3):
        ann.search(jnp.asarray(np.roll(small_corpus[:32], 1, axis=0)),
                   k=10, depth=10)
    assert pl._pipeline_search._cache_size() == size
    # sorted scores, ids valid
    s = np.asarray(s)
    assert (np.diff(s, axis=1) <= 1e-6).all()
    assert ((np.asarray(i) >= 0) & (np.asarray(i) < v.shape[0])).all()


def test_graph_filtered_traversal_parity(small_corpus):
    """10%-selectivity predicate: masked nodes stay traversable (recall
    matches filtered brute force within 0.01) but are NEVER emitted."""
    rng = np.random.default_rng(7)
    v = jnp.asarray(small_corpus)
    n = v.shape[0]
    ann = AnnIndex.build(v, WIDE)
    q = jnp.asarray(_corpus(16, 64, seed=3))
    mask = rng.random(n) < 0.10
    filt = jnp.asarray(mask.astype(np.int32))
    kept = np.flatnonzero(mask)
    _, gt_i = bruteforce.exact_topk(v[jnp.asarray(kept)], q, 10,
                                    use_kernel=False)
    gt_global = kept[np.asarray(gt_i)]
    s, i = ann.search(q, k=10, depth=10, filt=filt)
    i = np.asarray(i)
    emitted = i[i >= 0]
    assert mask[emitted].all(), "masked doc emitted"
    rec = float(ev.recall_at(jnp.asarray(gt_global), jnp.asarray(i)))
    assert rec >= 0.99, rec
    # connectivity: every query fills all k slots from the 10% subset
    assert (i >= 0).all()


def test_graph_segmented_matches_monolithic(small_corpus):
    """Segment lifecycle parity (the acceptance gate): 4 segments + 10%
    deletes, before AND after force-merge, recall@10 within 0.01 of a
    monolithic rebuild over the same live rows at the same ef."""
    rng = np.random.default_rng(5)
    v = np.asarray(small_corpus)
    n = v.shape[0]
    cfg = GraphConfig(ef=192, beam=8)
    w = IndexWriter(cfg)
    for chunk in np.array_split(v, 4):
        w.add(chunk)
        w.flush()
    dels = rng.choice(n, n // 10, replace=False)
    w.delete(dels.tolist())
    live = np.ones(n, bool)
    live[dels] = False
    q = jnp.asarray(_corpus(16, 64, seed=9))
    mono = AnnIndex.build(jnp.asarray(v[live]), cfg)
    oracle = AnnIndex.build(jnp.asarray(v[live]), BruteForceConfig())
    _, gt_i = oracle.search(q, k=10, depth=10)
    _, mono_i = mono.search(q, k=10, depth=100)
    r_mono = float(ev.recall_at(gt_i, mono_i[:, :10]))

    gid_to_live = -np.ones(n, np.int64)
    gid_to_live[live] = np.arange(live.sum())
    reader = w.refresh()
    _, seg_i = reader.search(q, k=10, depth=100)
    seg_i = np.asarray(seg_i)
    assert not np.isin(seg_i[seg_i >= 0], dels).any(), "deleted doc emitted"
    seg_live = np.where(seg_i >= 0, gid_to_live[np.maximum(seg_i, 0)], -1)
    r_seg = float(ev.recall_at(gt_i, jnp.asarray(seg_live[:, :10])))
    assert abs(r_seg - r_mono) <= 0.01, (r_seg, r_mono)

    # merge compacts + remaps ids: merged global ids == live-row order
    w.force_merge(1)
    merged = w.refresh()
    assert merged.num_segments == 1
    _, mrg_i = merged.search(q, k=10, depth=100)
    r_mrg = float(ev.recall_at(gt_i, jnp.asarray(np.asarray(mrg_i)[:, :10])))
    assert abs(r_mrg - r_mono) <= 0.01, (r_mrg, r_mono)


def test_graph_save_load_roundtrip(tmp_path, small_corpus):
    """hnsw persists through the npz+JSON format: loaded index returns
    bitwise-identical results and the same config."""
    v = jnp.asarray(small_corpus[:512])
    ann = AnnIndex.build(v, GraphConfig(ef=64, beam=4))
    path = str(tmp_path / "g.ann")
    ann.save(path)
    back = AnnIndex.load(path)
    assert back.method == "hnsw"
    assert back.config == ann.config
    q = jnp.asarray(small_corpus[:8])
    s1, i1 = ann.search(q, k=10, depth=10)
    s2, i2 = back.search(q, k=10, depth=10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_graph_scored_candidates_sublinear(small_corpus):
    """Per-query scored-candidate count is bounded by the traversal shape
    (entries + iters * beam * degree), independent of corpus size — the
    sublinearity the Pareto gate in BENCH_9 reports."""
    cfg = GraphConfig(ef=64, beam=4)
    q = jnp.asarray(small_corpus[:8])
    counts = {}
    for n in (1000, 2000):
        v = bruteforce.l2_normalize(jnp.asarray(small_corpus[:n]))
        nb, entry = graph.build_graph(v, cfg)
        _, _, scored = graph.search_graph(
            v, nb, entry, bruteforce.l2_normalize(q), 10,
            ef=cfg.ef, beam=cfg.beam, iters=cfg.search_iters, n_docs=n,
            use_kernel=False, with_stats=True)
        counts[n] = int(np.asarray(scored).max())
    bound = cfg.entries + cfg.search_iters * cfg.beam * cfg.total_degree
    assert counts[1000] <= bound and counts[2000] <= bound, (counts, bound)
    # doubling N must not double the work
    assert counts[2000] <= int(1.2 * counts[1000]) + bound // 10, counts


def test_graph_sharded_build_parity():
    """Distributed build (ring neighbor-exchange under shard_map, 8 fake
    host devices) produces the SAME adjacency and entry points as the
    single-device build — subprocess so this process's jax init stays
    single-device."""
    body = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import distributed
        from repro.core.graph import build_graph
        from repro.core.types import GraphConfig
        rng = np.random.default_rng(0)
        v = rng.normal(size=(1024, 64)).astype(np.float32)
        cfg = GraphConfig(ef=128, beam=8)
        mesh = jax.make_mesh((8,), ("data",))
        idx = distributed.build_sharded(mesh, jnp.asarray(v), cfg, ("data",))
        vn = jnp.asarray(v)
        vn = vn / jnp.linalg.norm(vn, axis=1, keepdims=True)
        nb, entry = build_graph(vn, cfg)
        assert np.array_equal(np.asarray(idx.neighbors), np.asarray(nb))
        assert np.array_equal(np.asarray(idx.entry), np.asarray(entry))
        print("sharded graph build parity ok")
    """)
    r = subprocess.run(
        [sys.executable, "-c", body],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=SRC),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"


def test_graph_sharded_search_raises():
    """Shard-local traversal is NOT the graph algorithm (edges cross shard
    boundaries); make_sharded_search must refuse loudly."""
    from repro.core import distributed

    with pytest.raises(TypeError, match="shard-local"):
        distributed.make_sharded_search(None, GraphConfig(), ("data",))
