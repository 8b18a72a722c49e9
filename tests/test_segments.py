"""Lucene-style segmented mutable index (core/segments.py, docs/DESIGN.md
§11): IndexWriter add/delete/flush/commit/merge, liveDocs masking inside the
match stage, generation-numbered commit points with v1 read-compat, and
epoch-invalidated serving.

The load-bearing property (the whole point of scoring every segment under
global collection statistics): a segmented index — any segment geometry,
with deletes — returns BITWISE the results of a fresh monolithic build of
the equivalent live corpus, for every encoding, before and after merges.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as pl
from repro.core.index import AnnIndex
from repro.core.segments import (
    IndexWriter,
    Segment,
    SegmentedAnnIndex,
    TieredMergePolicy,
    find_commits,
)
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
)
from repro.serve.ann_service import AnnService, AnnServiceConfig

ALL_CONFIGS = [
    FakeWordsConfig(quantization=50),
    FakeWordsConfig(quantization=50, scoring="dot"),
    LexicalLshConfig(buckets=64, hashes=2),
    KdTreeConfig(dims=8, backend="scan"),
    KdTreeConfig(dims=8, backend="scan", reduction="ppa-pca-ppa"),
    BruteForceConfig(),
]


def _ids(cfg):
    tag = type(cfg).__name__
    if isinstance(cfg, FakeWordsConfig):
        tag = f"fakewords-{cfg.scoring}"
    if isinstance(cfg, KdTreeConfig):
        tag = f"kdtree-{cfg.reduction}"
    return tag


def _corpora(rng):
    a = rng.normal(size=(600, 32)).astype(np.float32)
    b = rng.normal(size=(412, 32)).astype(np.float32)
    return a, b


def _map_mono_ids(gmap, mono_ids):
    """Monolithic live-corpus ids -> segmented stable global ids."""
    mono_ids = np.asarray(mono_ids)
    return np.where(mono_ids >= 0, gmap[np.maximum(mono_ids, 0)], -1)


def _assert_parity(reader, mono, queries, k=10, depth=50):
    """Segmented search == monolithic search on the live corpus: scores
    bitwise, ids exact (through the live-id mapping), rerank on AND off."""
    gmap = reader.live_global_ids()
    for rerank in (False, True):
        s0, i0 = mono.search(queries, k=k, depth=depth, rerank=rerank,
                             use_kernel=False)
        s1, i1 = reader.search(queries, k=k, depth=depth, rerank=rerank,
                               use_kernel=False)
        np.testing.assert_array_equal(
            _map_mono_ids(gmap, np.asarray(i0)), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


# -- the acceptance flow: add / add / delete / commit / reload / merge -------


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_segmented_equals_monolithic_with_deletes(cfg, rng, tmp_path):
    """Build corpus A, writer.add corpus B, delete a random 10%, commit,
    reload — results identical to a fresh monolithic build of the live
    corpus (scores bitwise, ids exact), before AND after a full merge."""
    a, b = _corpora(rng)
    queries = jnp.asarray(a[:8])
    w = IndexWriter(cfg, merge_policy=None)
    ids_a = w.add(a)
    assert w.flush() and w.num_segments == 1
    ids_b = w.add(b)
    np.testing.assert_array_equal(ids_a, np.arange(len(a)))
    np.testing.assert_array_equal(ids_b, np.arange(len(a), len(a) + len(b)))
    n = len(a) + len(b)
    dead = rng.choice(n, size=n // 10, replace=False)
    assert w.delete(dead) == len(dead)
    assert w.delete(dead) == 0  # idempotent

    live = np.ones(n, bool)
    live[dead] = False
    mono = AnnIndex.build(jnp.asarray(np.concatenate([a, b])[live]), cfg)

    path = os.path.join(tmp_path, "seg.ann")
    gen = w.commit(path)
    assert gen == 1
    reader = SegmentedAnnIndex.load(path)
    assert reader.num_segments == 2
    assert reader.num_docs == live.sum() and reader.max_doc == n
    np.testing.assert_array_equal(reader.live_global_ids(), np.flatnonzero(live))
    _assert_parity(reader, mono, queries)

    # forced full merge: one fully-live segment, ids now == monolithic ids
    w.force_merge(1)
    merged = w.refresh()
    assert merged.num_segments == 1 and merged.del_count == 0
    assert merged.num_docs == live.sum()
    _assert_parity(merged, mono, queries)
    # and the merged commit round-trips too
    gen2 = w.commit()
    assert gen2 == 2
    _assert_parity(SegmentedAnnIndex.load(path), mono, queries)


@pytest.mark.parametrize(
    "cfg",
    [FakeWordsConfig(quantization=50), BruteForceConfig()],
    ids=_ids,
)
def test_segmented_parity_on_kernel_path(cfg, rng):
    """The fused-kernel match path (interpret mode on CPU) preserves the
    same segmented-vs-monolithic parity."""
    a, b = _corpora(rng)
    a, b = a[:256], b[:200]
    queries = jnp.asarray(a[:4])
    w = IndexWriter(cfg, merge_policy=None)
    w.add(a)
    w.flush()
    w.add(b)
    n = len(a) + len(b)
    dead = rng.choice(n, size=n // 10, replace=False)
    w.delete(dead)
    live = np.ones(n, bool)
    live[dead] = False
    mono = AnnIndex.build(jnp.asarray(np.concatenate([a, b])[live]), cfg)
    reader = w.refresh()
    gmap = reader.live_global_ids()
    s0, i0 = mono.search(queries, k=10, depth=40, rerank=True, use_kernel=True)
    s1, i1 = reader.search(queries, k=10, depth=40, rerank=True, use_kernel=True)
    np.testing.assert_array_equal(_map_mono_ids(gmap, np.asarray(i0)), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_one_segment_equals_many_segments_after_merge(cfg, rng):
    """Same corpus via one flush == via N flushes + full merge, bit-for-bit
    (the merge rebuilds from stored normalized originals without drift)."""
    a, b = _corpora(rng)
    corpus = np.concatenate([a, b])
    queries = jnp.asarray(a[:8])
    w1 = IndexWriter(cfg, merge_policy=None)
    w1.add(corpus)
    one = w1.refresh()
    wn = IndexWriter(cfg, merge_policy=None)
    for chunk in np.array_split(corpus, 4):
        wn.add(chunk)
        wn.flush()
    assert wn.num_segments == 4
    wn.force_merge(1)
    many = wn.refresh()
    assert many.num_segments == 1
    for rerank in (False, True):
        s0, i0 = one.search(queries, k=10, depth=50, rerank=rerank, use_kernel=False)
        s1, i1 = many.search(queries, k=10, depth=50, rerank=rerank, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


# -- deletes -----------------------------------------------------------------


def test_delete_commit_load_round_trip(rng, tmp_path):
    """Deletes persist through commit points; deleted docs never surface;
    later generations stack further deletes."""
    a, _ = _corpora(rng)
    cfg = BruteForceConfig()
    path = os.path.join(tmp_path, "del.ann")
    w = IndexWriter(cfg, path=path, merge_policy=None)
    w.add(a)
    w.commit()
    # delete the exact nearest neighbors of the first 4 queries
    queries = jnp.asarray(a[:4])
    _, top = AnnIndex.build(jnp.asarray(a), cfg).search(
        queries, k=1, depth=1, use_kernel=False)
    victims = np.asarray(top)[:, 0]
    w.delete(victims)
    gen = w.commit()
    assert gen == 2
    loaded = SegmentedAnnIndex.load(path)
    assert loaded.del_count == len(set(victims.tolist()))
    _, ids = loaded.search(queries, k=10, depth=50, rerank=True, use_kernel=False)
    assert not set(victims.tolist()) & set(np.asarray(ids).ravel().tolist())
    # the pre-delete generation is still readable (point-in-time commits)
    old = SegmentedAnnIndex.load(path, generation=1)
    assert old.del_count == 0
    _, old_ids = old.search(queries, k=1, depth=1, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(old_ids)[:, 0], victims)


def test_delete_in_buffer_and_depth_semantics(rng):
    """Deleting buffered (unflushed) docs works, and liveDocs masking keeps
    depth semantics: depth-d still returns d LIVE candidates when d live
    docs exist (deletes masked inside the match stage, not post-filtered)."""
    a, _ = _corpora(rng)
    w = IndexWriter(BruteForceConfig(), merge_policy=None)
    ids = w.add(a)
    w.delete(ids[10:20])  # still in the buffer
    reader = w.refresh()
    assert reader.del_count == 10
    q = jnp.asarray(a[:2])
    depth = len(a) - 10  # exactly the live count
    s, i = reader.search(q, k=depth, depth=depth, use_kernel=False)
    ids_np = np.asarray(i)
    assert (ids_np >= 0).all(), "masked deletes must not shrink the depth"
    assert not (np.isin(ids_np, np.arange(10, 20))).any()
    with pytest.raises(IndexError):
        w.delete([len(a) + 5])


def test_live_docs_matcher_is_a_match_stage(rng):
    """LiveDocsMatcher unit semantics: masking happens before the stage's
    top-k, so the output is the top-depth over LIVE docs only."""
    v = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    ann = AnnIndex.build(v, BruteForceConfig())
    q = v[:1]
    inner = pl.make_matcher(BruteForceConfig())
    s_all, i_all = inner(ann.index, q, 64, use_kernel=False)
    top = np.asarray(i_all)[0]
    live = np.ones(64, bool)
    live[top[:3]] = False  # kill the 3 best docs
    m = pl.LiveDocsMatcher(inner=inner, extra=4)
    s, i = m(ann.index, q, 5, jnp.asarray(live), use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i)[0], top[3:8])


# -- merge policy ------------------------------------------------------------


def test_tiered_merge_policy_geometry():
    pol = TieredMergePolicy(merge_factor=4, floor_docs=100)
    assert pol.tier(50) == 0 and pol.tier(100) == 0
    assert pol.tier(101) == 1 and pol.tier(400) == 1 and pol.tier(401) == 2

    def seg(n_live, n_total=None):
        n_total = n_total if n_total is not None else n_live
        live = np.zeros(n_total, bool)
        live[:n_live] = True
        ann = AnnIndex.build(
            jnp.zeros((n_total, 4)) + np.arange(n_total)[:, None],
            BruteForceConfig())
        return Segment(ann=ann, live=live, name="t")

    # 3 same-tier segments: stable; 4: merge the run
    assert pol.find_merge([seg(50)] * 3) is None
    assert pol.find_merge([seg(50)] * 4) == (0, 4)
    # adjacent-only: a tier-1 segment breaks the run
    assert pol.find_merge([seg(50), seg(50), seg(200), seg(50), seg(50)]) is None
    # expunge: >= 50% deleted is rewritten alone
    assert pol.find_merge([seg(200), seg(40, 100)]) == (1, 2)


def test_writer_auto_merge_and_id_remap(rng):
    """Flush-triggered tiered merging keeps the segment count logarithmic,
    and a merge drops deleted rows and remaps ids compactly."""
    a, _ = _corpora(rng)
    w = IndexWriter(
        BruteForceConfig(),
        merge_policy=TieredMergePolicy(merge_factor=4, floor_docs=128),
    )
    for chunk in np.array_split(a[:512], 8):  # 8 x 64-doc flushes
        w.add(chunk)
        w.flush()
    assert w.num_segments <= 3  # 8 floor flushes collapse through the tiers
    total_before = w.total_docs
    w.delete(np.arange(0, 32))
    w.force_merge(1)
    assert w.num_segments == 1
    assert w.total_docs == total_before - 32  # dead rows really dropped
    reader = w.refresh()
    assert reader.num_docs == total_before - 32 and reader.del_count == 0


def test_merge_fully_dead_segments_are_dropped(rng):
    a, _ = _corpora(rng)
    w = IndexWriter(BruteForceConfig(), merge_policy=None)
    ids = w.add(a[:64])
    w.flush()
    w.add(a[64:128])
    w.flush()
    w.delete(ids)  # first segment fully dead
    w.force_merge(1)
    assert w.num_segments == 1 and w.total_docs == 64
    reader = w.refresh()
    np.testing.assert_array_equal(reader.live_global_ids(), np.arange(64))


# -- epoch-keyed serving -----------------------------------------------------


def test_service_nrt_refresh_zero_stale_hits(rng):
    """AnnService(writer=...) serves across refresh() with ZERO stale cache
    hits: a doc added after the first query round must surface immediately
    post-refresh even with the result cache on."""
    a, _ = _corpora(rng)
    w = IndexWriter(BruteForceConfig(), merge_policy=None)
    w.add(a)
    svc = AnnService(writer=w, service=AnnServiceConfig(
        k=5, depth=20, rerank=True, max_batch=8, cache_size=16))
    qs = a[:8]
    _, i1 = svc.search_batch(qs)
    _, i1b = svc.search_batch(qs)
    assert svc.cache_hits == 1  # warm within an epoch
    np.testing.assert_array_equal(i1, i1b)
    # a near-duplicate of query 0: the new exact-match doc must win
    new_id = int(w.add(a[0:1] * 3.0)[0])
    old_epoch = svc.ann.epoch
    new_epoch = svc.refresh()
    assert new_epoch != old_epoch
    _, i2 = svc.search_batch(qs)
    assert new_id in np.asarray(i2)[0]
    # deletes invalidate the same way
    w.delete([new_id])
    svc.refresh()
    _, i3 = svc.search_batch(qs)
    assert new_id not in np.asarray(i3)
    # zero stale hits: every post-mutation answer was recomputed
    assert svc.cache_hits == 1 and svc.cache_misses == 3
    # an unchanged refresh keeps the epoch AND the warm cache
    assert svc.refresh() == svc.ann.epoch
    _, i3b = svc.search_batch(qs)
    np.testing.assert_array_equal(i3, i3b)
    assert svc.cache_hits == 2
    stats = svc.stats()
    assert stats["segments"] == svc.ann.num_segments
    assert stats["epoch"] == svc.ann.epoch


def test_service_cache_key_includes_index_epoch(small_corpus):
    """Regression: _cache_key used to omit index identity — a service whose
    index was swapped in place kept serving the OLD index's cached
    results."""
    v = jnp.asarray(small_corpus[:512])
    cfg = BruteForceConfig()
    ann1 = AnnIndex.build(v, cfg)
    ann2 = AnnIndex.build(jnp.asarray(small_corpus[:512][::-1].copy()), cfg)
    assert ann1.epoch != ann2.epoch
    svc = AnnService(ann1, AnnServiceConfig(
        k=5, depth=20, rerank=True, max_batch=8, cache_size=8))
    qs = small_corpus[:8]
    _, ia = svc.search_batch(qs)
    assert svc.set_index(ann2) == ann2.epoch
    _, ib = svc.search_batch(qs)
    assert svc.cache_hits == 0, "stale hit across an index swap"
    assert not np.array_equal(ia, ib)
    # swapping back revives the first index's still-resident entries
    svc.set_index(ann1)
    _, ic = svc.search_batch(qs)
    assert svc.cache_hits == 1
    np.testing.assert_array_equal(ia, ic)


def test_service_serves_segmented_index_directly(rng):
    """A SegmentedAnnIndex (e.g. loaded from a commit point) serves through
    AnnService like any index; unsupported combos fail loudly."""
    a, _ = _corpora(rng)
    w = IndexWriter(FakeWordsConfig(quantization=50), merge_policy=None)
    w.add(a[:300])
    w.flush()
    w.add(a[300:])
    reader = w.refresh()
    svc = AnnService(reader, AnnServiceConfig(
        k=10, depth=50, rerank=True, max_batch=8))
    s_svc, i_svc = svc.search_batch(a[:8])
    s_dir, i_dir = reader.search(
        jnp.asarray(a[:8]), k=10, depth=50, rerank=True, use_kernel=None)
    np.testing.assert_array_equal(np.asarray(i_dir), i_svc)
    np.testing.assert_array_equal(np.asarray(s_dir), s_svc)
    # blockmax now rides the packed superbuffer for fake-words/LSH
    # (tests/test_serve.py); encodings without blockmax bounds still fail
    # loudly at bind time.
    w_bf = IndexWriter(BruteForceConfig(), merge_policy=None)
    w_bf.add(a[:64])
    w_bf.flush()
    with pytest.raises(ValueError):
        AnnService(w_bf.refresh(), AnnServiceConfig(blockmax_keep=4))
    with pytest.raises(TypeError):
        svc.set_index("not an index")  # type: ignore[arg-type]


def test_max_wait_s_is_back():
    """``max_wait_s`` returned as the async micro-batcher's coalescing SLO
    (docs/DESIGN.md §14): positive default, paired with a bounded admission
    queue."""
    assert AnnServiceConfig().max_wait_s > 0
    assert AnnServiceConfig().queue_depth > 0


# -- persistence formats -----------------------------------------------------


def test_commit_points_are_generation_numbered_and_atomic(rng, tmp_path):
    a, _ = _corpora(rng)
    path = os.path.join(tmp_path, "gen.ann")
    w = IndexWriter(BruteForceConfig(), path=path, merge_policy=None)
    w.add(a[:100])
    assert w.commit() == 1
    w.add(a[100:200])
    assert w.commit() == 2
    assert [g for g, _ in find_commits(path)] == [1, 2]
    with open(os.path.join(path, "segments_2.json")) as f:
        meta = json.load(f)
    assert meta["format_version"] == 2 and meta["generation"] == 2
    assert len(meta["segments"]) == 2
    # segment dirs are immutable: gen-2 reuses gen-1's segment dir
    assert meta["segments"][0]["name"] == "seg0"
    # no torn tmp files left behind
    assert not [f for f in os.listdir(path) if f.endswith(".tmp")]
    r1 = SegmentedAnnIndex.load(path, generation=1)
    r2 = SegmentedAnnIndex.load(path)
    assert (r1.num_docs, r2.num_docs) == (100, 200)
    with pytest.raises(FileNotFoundError):
        SegmentedAnnIndex.load(path, generation=7)


def test_commit_lineage_guard(rng, tmp_path):
    """A writer that never read a directory's commits must not commit over
    them (its segment names would collide with the foreign dirs and the
    new manifest would silently reference another writer's data);
    IndexWriter.open adopts the lineage and may continue it."""
    a, _ = _corpora(rng)
    path = os.path.join(tmp_path, "lineage.ann")
    w1 = IndexWriter(BruteForceConfig(), merge_policy=None)
    w1.add(a[:64])
    assert w1.commit(path) == 1
    w2 = IndexWriter(BruteForceConfig(), merge_policy=None)
    w2.add(a[64:128])
    with pytest.raises(ValueError, match="foreign commit history"):
        w2.commit(path)
    # the durable state is untouched and still opens at gen 1
    assert [g for g, _ in find_commits(path)] == [1]
    w3 = IndexWriter.open(path)
    w3.add(a[64:128])
    assert w3.commit() == 2
    assert SegmentedAnnIndex.load(path).num_docs == 128


def test_v1_dir_loads_as_single_segment_and_upgrades(rng, tmp_path):
    """v1 read-compat: a plain AnnIndex.save dir opens as one fully-live
    segment, and IndexWriter.open upgrades it to the segmented lifecycle."""
    a, _ = _corpora(rng)
    cfg = FakeWordsConfig(quantization=50)
    ann = AnnIndex.build(jnp.asarray(a), cfg)
    path = os.path.join(tmp_path, "v1.ann")
    ann.save(path)
    reader = SegmentedAnnIndex.load(path)
    assert reader.num_segments == 1 and reader.num_docs == len(a)
    with pytest.raises(FileNotFoundError, match="v1 single-index"):
        SegmentedAnnIndex.load(path, generation=3)
    qs = jnp.asarray(a[:8])
    s0, i0 = ann.search(qs, k=10, depth=50, rerank=True, use_kernel=False)
    s1, i1 = reader.search(qs, k=10, depth=50, rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    w = IndexWriter.open(path)
    w.add(a[:10])
    w.delete([0])
    gen = w.commit()
    upgraded = SegmentedAnnIndex.load(path)
    assert gen == 1 and upgraded.num_segments == 2
    assert upgraded.num_docs == len(a) + 10 - 1


def test_format_version_is_validated(rng, tmp_path):
    """Satellite bugfix: AnnIndex.load fails with a clear 'newer format'
    error instead of a KeyError deep in _rebuild_index; commit points
    validate the same way; a commit dir pointed at AnnIndex.load explains
    itself."""
    a, _ = _corpora(rng)
    path = os.path.join(tmp_path, "fv.ann")
    ann = AnnIndex.build(jnp.asarray(a[:64]), BruteForceConfig())
    ann.save(path)
    cfg_path = os.path.join(path, "config.json")
    with open(cfg_path) as f:
        meta = json.load(f)
    meta["format_version"] = 99
    with open(cfg_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="format_version 99.*newer"):
        AnnIndex.load(path)

    seg_path = os.path.join(tmp_path, "seg.ann")
    w = IndexWriter(BruteForceConfig(), path=seg_path, merge_policy=None)
    w.add(a[:64])
    w.commit()
    with pytest.raises(ValueError, match="segmented commit point"):
        AnnIndex.load(seg_path)
    commit_file = os.path.join(seg_path, "segments_1.json")
    with open(commit_file) as f:
        meta = json.load(f)
    meta["format_version"] = 99
    with open(commit_file, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="format_version 99"):
        SegmentedAnnIndex.load(seg_path)


# -- guard rails -------------------------------------------------------------


def test_writer_guard_rails(rng):
    a, _ = _corpora(rng)
    with pytest.raises(ValueError, match="rerank_store"):
        IndexWriter(BruteForceConfig(), rerank_store="fp16")
    with pytest.raises(ValueError, match="backend='scan'"):
        IndexWriter(KdTreeConfig(dims=8, backend="tree"))
    w = IndexWriter(BruteForceConfig(), merge_policy=None)
    with pytest.raises(ValueError):
        w.add(np.zeros((0, 8), np.float32))
    with pytest.raises(ValueError, match="no live docs"):
        w.refresh().search(jnp.asarray(a[:1]))
    with pytest.raises(ValueError, match="commit needs a path"):
        w.commit()
    w.add(a[:64])
    reader = w.refresh()
    with pytest.raises(ValueError, match=r"IndexWriter\(shards=N\)"):
        AnnService(reader, mesh=object())  # type: ignore[arg-type]


def test_auto_flush_on_buffer_threshold(rng):
    a, _ = _corpora(rng)
    w = IndexWriter(
        BruteForceConfig(), merge_policy=None, max_buffered_docs=128)
    for chunk in np.array_split(a[:512], 16):  # 32 docs per add
        w.add(chunk)
    assert w.num_segments == 4 and w.buffered_docs == 0
