"""The unified staged retrieval path: SearchPipeline / AnnIndex / AnnService
serve every encoding through one code path, and indexes persist."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, fakewords, kdtree, lexical_lsh
from repro.core import pipeline as pl
from repro.core.index import AnnIndex
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
    SearchParams,
)
from repro.serve.ann_service import AnnService, AnnServiceConfig

ALL_CONFIGS = [
    FakeWordsConfig(quantization=50),
    FakeWordsConfig(quantization=50, scoring="dot"),
    LexicalLshConfig(buckets=64, hashes=2),
    KdTreeConfig(dims=8, backend="scan"),
    BruteForceConfig(),
]


def _ids(name):
    if isinstance(name, FakeWordsConfig):
        return f"fakewords-{name.scoring}"
    return type(name).__name__


# -- service == facade over every encoding -----------------------------------


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_ann_service_matches_ann_index(small_corpus, cfg):
    """The serving layer must return exactly what the AnnIndex facade
    returns for ANY encoding — one retrieval architecture, no per-method
    serving branches."""
    v = jnp.asarray(small_corpus)
    qs = small_corpus[:24]
    ann = AnnIndex.build(v, cfg)
    s_direct, i_direct = ann.search(
        jnp.asarray(qs), k=10, depth=100, rerank=True, use_kernel=False)
    svc = AnnService(ann, AnnServiceConfig(
        k=10, depth=100, rerank=True, max_batch=8, use_kernel=False))
    s_srv, i_srv = svc.search_batch(qs)
    np.testing.assert_array_equal(np.asarray(i_direct), i_srv)
    np.testing.assert_array_equal(np.asarray(s_direct), s_srv)
    stats = svc.stats()
    assert stats["queries"] == 24 and stats["method"] == ann.method


def test_ann_service_raw_index_back_compat(small_corpus):
    """AnnService(raw_index, method_config, service_config) still works."""
    v = jnp.asarray(small_corpus)
    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(v, cfg)
    svc = AnnService(idx, cfg, AnnServiceConfig(k=5, depth=50, max_batch=16))
    s, ids = svc.search_batch(small_corpus[:16])
    assert ids.shape == (16, 5)


def test_ann_service_inherits_index_level_knobs(small_corpus):
    """Regression: an AnnIndex carrying its own blockmax/use_kernel knobs
    (e.g. loaded from disk) must serve with them even when the service
    config leaves them unset — this used to crash with min(None, int)."""
    v = jnp.asarray(small_corpus[:512])
    ann = AnnIndex.build(
        v, FakeWordsConfig(quantization=40),
        blockmax_keep=4, blockmax_block_size=64, use_kernel=False)
    svc = AnnService(ann, AnnServiceConfig(k=10, depth=50, rerank=False, max_batch=8))
    s_srv, i_srv = svc.search_batch(small_corpus[:8])
    assert svc._bm is ann.bm  # reuses the index's structure, no rebuild
    s_d, i_d = ann.search(jnp.asarray(small_corpus[:8]), k=10, depth=50)
    np.testing.assert_array_equal(np.asarray(i_d), i_srv)
    # the service config still wins when it sets its own knobs
    svc2 = AnnService(ann, AnnServiceConfig(
        k=10, depth=50, rerank=False, max_batch=8,
        blockmax_keep=2, blockmax_block_size=128))
    assert svc2._bm.block_size == 128 and svc2._bm_keep == 2
    svc2.search_batch(small_corpus[:8])


def test_ann_service_latency_stats(small_corpus):
    v = jnp.asarray(small_corpus)
    svc = AnnService(
        AnnIndex.build(v, FakeWordsConfig(quantization=50)),
        AnnServiceConfig(k=10, depth=50, max_batch=8),
    )
    assert svc.stats()["lat_p50_ms"] is None  # nothing served yet
    buckets = len(svc._lat.counts)
    svc.search_batch(small_corpus[:48])  # 6 launches, every one recorded
    stats = svc.stats()
    assert stats["batches"] == 6 and svc._lat.n == 6
    assert len(svc._lat.counts) == buckets  # fixed buckets, not a growing log
    assert stats["lat_p50_ms"] > 0 and stats["lat_p99_ms"] >= stats["lat_p50_ms"]
    svc.reset_latency()  # warmup exclusion hook: drops latencies, not counts
    assert svc.stats()["lat_p50_ms"] is None and svc.stats()["batches"] == 6


# -- persistence -------------------------------------------------------------


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_save_load_search_bit_for_bit(small_corpus, cfg, tmp_path):
    """A save->load round trip must preserve search output exactly for
    every index type (scores AND ids, rerank on and off)."""
    v = jnp.asarray(small_corpus)
    qs = jnp.asarray(small_corpus[:16])
    ann = AnnIndex.build(v, cfg)
    path = os.path.join(tmp_path, "idx.ann")
    ann.save(path)
    loaded = AnnIndex.load(path)
    assert loaded.method == ann.method
    assert loaded.config == ann.config
    for params in (SearchParams(k=10, depth=100),
                   SearchParams(k=10, depth=100, rerank=True)):
        s0, i0 = ann.search(qs, params=params, use_kernel=False)
        s1, i1 = loaded.search(qs, params=params, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_save_load_kdtree_ppa_and_tree_backend(small_corpus, tmp_path):
    """The nested PPA->PCA->PPA reduction model and the tree-backend arrays
    survive the round trip."""
    v = jnp.asarray(small_corpus[:512])
    cfg = KdTreeConfig(dims=8, backend="tree", reduction="ppa-pca-ppa")
    ann = AnnIndex.build(v, cfg)
    path = os.path.join(tmp_path, "kd.ann")
    ann.save(path)
    loaded = AnnIndex.load(path)
    qs = jnp.asarray(small_corpus[:8])
    s0, i0 = ann.search(qs, k=5, depth=20)
    s1, i1 = loaded.search(qs, k=5, depth=20)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_save_load_preserves_blockmax_knobs(small_corpus, tmp_path):
    """Serving knobs (blockmax_keep / block size / use_kernel) persist and
    the blockmax structure is rebuilt identically on load."""
    v = jnp.asarray(small_corpus[:512])
    ann = AnnIndex.build(
        v, FakeWordsConfig(quantization=40),
        blockmax_keep=4, blockmax_block_size=64, use_kernel=False)
    path = os.path.join(tmp_path, "bm.ann")
    ann.save(path)
    loaded = AnnIndex.load(path)
    assert loaded.blockmax_keep == 4 and loaded.blockmax_block_size == 64
    assert loaded.use_kernel is False
    assert loaded.bm is not None and loaded.bm.num_blocks == ann.bm.num_blocks
    qs = jnp.asarray(small_corpus[:8])
    s0, i0 = ann.search(qs, k=10, depth=50)
    s1, i1 = loaded.search(qs, k=10, depth=50)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    # and the knobs can be overridden at load time
    dense = AnnIndex.load(path, blockmax_keep=None)
    assert dense.bm is None


# -- pipeline parity with the per-method wrappers ----------------------------


def test_pipeline_matches_method_wrappers(small_corpus):
    """AnnIndex.search (the pipeline) must agree exactly with the thin
    per-method search() wrappers — no scoring drift through the refactor."""
    v = jnp.asarray(small_corpus)
    q = jnp.asarray(small_corpus[:16])
    qn = bruteforce.l2_normalize(q)

    cfg = FakeWordsConfig(quantization=50)
    ann = AnnIndex.build(v, cfg)
    q_tf = fakewords.encode_queries(qn, cfg, normalized=True)
    s_w, i_w = fakewords.search(
        ann.index, q_tf, qn, k=10, depth=100, rerank=True, use_kernel=False)
    s_p, i_p = ann.search(q, k=10, depth=100, rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_w), np.asarray(i_p))
    np.testing.assert_array_equal(np.asarray(s_w), np.asarray(s_p))

    lcfg = LexicalLshConfig(buckets=64, hashes=2)
    ann_l = AnnIndex.build(v, lcfg)
    sig_q = lexical_lsh.encode(qn, lcfg)
    s_w, i_w = lexical_lsh.search(
        ann_l.index, sig_q, qn, k=10, depth=100, rerank=True, use_kernel=False)
    s_p, i_p = ann_l.search(q, k=10, depth=100, rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_w), np.asarray(i_p))

    kcfg = KdTreeConfig(dims=8, backend="scan")
    ann_k = AnnIndex.build(v, kcfg)
    s_w, i_w = kdtree.search(
        ann_k.index, qn, k=10, depth=100, rerank=True, normalized=True,
        use_kernel=False)
    s_p, i_p = ann_k.search(q, k=10, depth=100, rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_w), np.asarray(i_p))


def test_bruteforce_pipeline_is_exact(small_corpus):
    v = jnp.asarray(small_corpus)
    q = jnp.asarray(small_corpus[:16])
    ann = AnnIndex.build(v, BruteForceConfig())
    s_p, i_p = ann.search(q, k=10, depth=10, use_kernel=False)
    s_e, i_e = bruteforce.exact_topk(v, q, 10, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_e))


def test_blockmax_through_facade_matches_pruned_search(small_corpus):
    v = jnp.asarray(small_corpus[:512])
    cfg = FakeWordsConfig(quantization=40)
    ann = AnnIndex.build(v, cfg, blockmax_keep=4, blockmax_block_size=64)
    from repro.core import blockmax

    qn = bruteforce.l2_normalize(jnp.asarray(small_corpus[:8]))
    q_tf = fakewords.encode_queries(qn, cfg, normalized=True)
    s_ref, i_ref = blockmax.pruned_search(
        ann.index, ann.bm, q_tf, n_keep=4, depth=50, use_kernel=False)
    s_p, i_p = ann.search(
        jnp.asarray(small_corpus[:8]), k=50, depth=50, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_p))


def test_pipeline_stages_are_static_hashable():
    """Stages and pipelines are frozen/hashable: valid jit static args."""
    p1 = pl.build_pipeline(FakeWordsConfig(quantization=50))
    p2 = pl.build_pipeline(FakeWordsConfig(quantization=50))
    assert p1 == p2 and hash(p1) == hash(p2)
    assert pl.make_matcher(LexicalLshConfig()) == pl.LshMatcher()
