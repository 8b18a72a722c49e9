"""The staged BuildPipeline (core/builder.py, docs/DESIGN.md §8): build
parity (local == sharded, wrapper == pipeline), the int8 QuantizedStore
rerank path, and the AnnService result cache.

Sharded scenarios run in subprocesses with 8 fake host devices (same
pattern as tests/test_distributed.py) so this process's single-device jax
init stays clean.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, builder, eval as ev, fakewords
from repro.core import pipeline as pl
from repro.core.index import AnnIndex
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
)
from repro.serve.ann_service import AnnService, AnnServiceConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_CONFIGS = [
    FakeWordsConfig(quantization=50),
    FakeWordsConfig(quantization=50, scoring="dot"),
    LexicalLshConfig(buckets=64, hashes=2),
    KdTreeConfig(dims=8, backend="scan"),
    BruteForceConfig(),
]


def _ids(cfg):
    if isinstance(cfg, FakeWordsConfig):
        return f"fakewords-{cfg.scoring}"
    return type(cfg).__name__


def run_subprocess(body: str):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        """
    ) + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=SRC),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# -- local BuildPipeline == the thin per-method wrappers ---------------------


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_build_pipeline_matches_wrappers_bit_for_bit(small_corpus, cfg):
    """make_build_pipeline(cfg).build_local must equal AnnIndex.build's
    index leaf-for-leaf (the wrappers ARE the pipeline)."""
    v = jnp.asarray(small_corpus[:512])
    a = builder.make_build_pipeline(cfg).build_local(v)
    b = AnnIndex.build(v, cfg).index
    import dataclasses

    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name == "reduction" or x is None:
            assert (x is None) == (y is None)
            continue
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=f.name)


def test_build_pipeline_stages_are_static_hashable():
    p1 = builder.make_build_pipeline(FakeWordsConfig(quantization=50))
    p2 = builder.make_build_pipeline(FakeWordsConfig(quantization=50))
    assert p1 == p2 and hash(p1) == hash(p2)
    assert builder.make_build_pipeline(LexicalLshConfig()).postings == builder.LshPostings()


def test_rerank_store_selection(small_corpus):
    v = jnp.asarray(small_corpus[:256])
    cfg = FakeWordsConfig(quantization=50)
    exact = AnnIndex.build(v, cfg, rerank_store="exact").index
    assert exact.vectors is not None and exact.vq is None
    q8 = AnnIndex.build(v, cfg, rerank_store="int8").index
    assert q8.vectors is None and q8.vq is not None
    assert q8.vq.q.dtype == jnp.int8 and q8.vq.scale.shape == (256,)
    none = AnnIndex.build(v, cfg, rerank_store="none").index
    assert none.vectors is None and none.vq is None
    # brute force keeps the fp32 match operand regardless of the store
    bf = AnnIndex.build(v, BruteForceConfig(), rerank_store="int8").index
    assert bf.vectors is not None and bf.vq is not None
    with pytest.raises(ValueError):
        builder.make_build_pipeline(cfg, "fp7")


# -- sharded build == local build (the acceptance bar) -----------------------


def test_sharded_build_parity_all_encodings():
    """For every encoding + bruteforce: the mesh-sharded BuildPipeline build
    equals the single-host build — bit-for-bit leaves for the row-local
    encodings, identical top-k ids (lowest-doc-id ties) and fp-tolerant
    scores through the SAME sharded search for the kd-tree (whose reduction
    is eigendecomposed from psum'd moments)."""
    run_subprocess("""
    from repro.core import bruteforce, distributed
    from repro.core.index import AnnIndex
    from repro.core.types import (BruteForceConfig, FakeWordsConfig,
                                  KdTreeConfig, LexicalLshConfig)
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(1024, 32)).astype(np.float32))
    qs = vecs[:8]
    qn = bruteforce.l2_normalize(qs)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    axes = ("data", "model")
    for cfg in (FakeWordsConfig(quantization=50),
                FakeWordsConfig(quantization=50, scoring="dot"),
                LexicalLshConfig(buckets=64, hashes=2),
                KdTreeConfig(dims=8, backend="scan"),
                KdTreeConfig(dims=8, backend="scan", reduction="ppa-pca-ppa"),
                BruteForceConfig()):
        local = AnnIndex.build(vecs, cfg)
        sh = distributed.build_sharded(mesh, vecs, cfg, axes)
        exact = not isinstance(cfg, KdTreeConfig)
        for f in dataclasses.fields(local.index):
            x, y = getattr(local.index, f.name), getattr(sh, f.name)
            if f.name == "reduction" or x is None:
                continue
            if exact:
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=f.name)
            elif f.name in ("reduced", "lifted", "vectors"):
                a_np, b_np = np.asarray(x), np.asarray(y)
                if f.name != "vectors":
                    # eigh's per-eigenvector sign is an arbitrary convention;
                    # align columns before comparing (L2 geometry invariant).
                    sign = np.sign(np.sum(a_np * b_np, axis=0))
                    sign[sign == 0] = 1.0
                    b_np = b_np * sign
                np.testing.assert_allclose(
                    a_np, b_np, atol=1e-4, err_msg=f.name)
        search = distributed.make_sharded_search(
            mesh, cfg, axes, k=10, depth=50, rerank=True)
        # Encode queries through EACH build's own model: eigh's eigenvector
        # signs are an arbitrary convention, so the sharded reduction may be
        # sign-flipped vs the local one — search results are invariant only
        # when queries project through the same model as the index.
        s_a, i_a = search(sh, AnnIndex(config=cfg, index=sh).encode_queries(qs), qn)
        s_b, i_b = search(
            distributed.shard_index(mesh, local.index, axes),
            local.encode_queries(qs), qn)
        np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b))
        np.testing.assert_allclose(
            np.asarray(s_a), np.asarray(s_b), rtol=1e-5, atol=1e-6)
        print("parity ok", type(cfg).__name__, getattr(cfg, "scoring", ""),
              getattr(cfg, "reduction", ""))
    """)


def test_sharded_quantized_rerank_end_to_end():
    """--quantized-rerank's pod path: sharded int8-store build, sharded
    search with the quantized local rerank gather, served through
    AnnService; recall@10 within 0.01 of the fp32-rerank service."""
    run_subprocess("""
    from repro.core import bruteforce, distributed, eval as ev
    from repro.core.index import AnnIndex
    from repro.core.types import FakeWordsConfig
    from repro.serve.ann_service import AnnService, AnnServiceConfig
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(2048, 32)).astype(np.float32))
    qs = np.asarray(vecs[:64]) + 0.01 * rng.normal(size=(64, 32)).astype(np.float32)
    cfg = FakeWordsConfig(quantization=50)
    mesh = jax.make_mesh((8,), ("data",))
    scfg = AnnServiceConfig(k=10, depth=100, rerank=True, max_batch=32)
    _, gt = bruteforce.exact_topk(vecs, jnp.asarray(qs), 10)
    recalls = {}
    for store in ("exact", "int8"):
        ann = AnnIndex.build(vecs, cfg, rerank_store=store,
                             mesh=mesh, shard_axes=("data",))
        assert (ann.index.vq is None) == (store == "exact")
        svc = AnnService(ann, scfg, mesh=mesh, shard_axes=("data",))
        _, ids = svc.search_batch(qs)
        recalls[store] = float(ev.recall_at(gt, jnp.asarray(ids)))
    print("recalls", recalls)
    assert recalls["exact"] > 0.9, recalls
    assert abs(recalls["exact"] - recalls["int8"]) <= 0.01, recalls
    """)


# -- QuantizedStore: quality, persistence, error bound -----------------------


def test_quantized_rerank_recall_within_001_of_fp32(small_corpus):
    """Acceptance: int8 rerank serves end-to-end through AnnService with
    recall@10 within 0.01 of fp32 rerank (single-device path)."""
    v = jnp.asarray(small_corpus)
    qs = small_corpus[:64] + 0.01 * np.random.default_rng(1).normal(
        size=(64, small_corpus.shape[1])).astype(np.float32)
    _, gt = bruteforce.exact_topk(v, jnp.asarray(qs), 10)
    scfg = AnnServiceConfig(k=10, depth=100, rerank=True, max_batch=32,
                            use_kernel=False)
    recalls = {}
    for store in ("exact", "int8"):
        ann = AnnIndex.build(v, FakeWordsConfig(quantization=50),
                             rerank_store=store)
        svc = AnnService(ann, scfg)
        _, ids = svc.search_batch(qs)
        recalls[store] = float(ev.recall_at(gt, jnp.asarray(ids)))
    assert recalls["exact"] > 0.9, recalls
    assert abs(recalls["exact"] - recalls["int8"]) <= 0.01, recalls


@pytest.mark.parametrize("cfg", ALL_CONFIGS, ids=_ids)
def test_quantized_store_save_load_bit_for_bit(small_corpus, cfg, tmp_path):
    """An int8-store index round-trips through save/load: the store, the
    quantized_rerank knob, and the search output all survive exactly."""
    v = jnp.asarray(small_corpus[:512])
    qs = jnp.asarray(small_corpus[:16])
    ann = AnnIndex.build(v, cfg, rerank_store="int8")
    assert ann.quantized_rerank
    assert isinstance(ann.pipeline.reranker, pl.QuantizedCosineReranker)
    path = os.path.join(tmp_path, "q.ann")
    ann.save(path)
    loaded = AnnIndex.load(path)
    assert loaded.quantized_rerank
    np.testing.assert_array_equal(
        np.asarray(loaded.index.vq.q), np.asarray(ann.index.vq.q))
    np.testing.assert_array_equal(
        np.asarray(loaded.index.vq.scale), np.asarray(ann.index.vq.scale))
    s0, i0 = ann.search(qs, k=10, depth=100, rerank=True, use_kernel=False)
    s1, i1 = loaded.search(qs, k=10, depth=100, rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def _check_int8_error_bound(n: int, d: int, seed: int) -> None:
    """Per-candidate int8 rerank score error is bounded by the quantization
    step: |q.v_hat - q.v| <= ||q||_1 * scale/2 (+fp slack), with
    v_hat = vq.q * vq.scale and unit-normalized queries."""
    rng = np.random.default_rng(seed)
    v = bruteforce.l2_normalize(
        jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)))
    q = bruteforce.l2_normalize(
        jnp.asarray(rng.normal(size=(4, d)).astype(np.float32)))
    vq = builder.quantize_store(v)
    cand = jnp.tile(jnp.arange(n, dtype=jnp.int32)[None, :], (4, 1))
    s_q = np.asarray(pl.candidate_scores(
        type("I", (), {"vq": vq, "vectors": None})(), q, cand, quantized=True))
    s_f = np.asarray(q @ v.T)
    bound = (
        np.sum(np.abs(np.asarray(q)), axis=1, keepdims=True)
        * np.asarray(vq.scale)[None, :] / 2.0
    )
    assert (np.abs(s_q - s_f) <= bound + 1e-5).all(), (
        np.max(np.abs(s_q - s_f) - bound))


def test_int8_rerank_error_bound_deterministic():
    for seed in range(8):
        _check_int8_error_bound(2 + 5 * seed, 3 + 7 * seed, seed)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(2, 32), st.integers(2, 48), st.integers(0, 2**31 - 1))
    def test_int8_rerank_error_bounded_by_quantization_step(n, d, seed):
        _check_int8_error_bound(n, d, seed)
except ImportError:  # pragma: no cover - hypothesis is a dev dependency
    pass


def test_service_honors_quantized_knob_when_both_stores_present(small_corpus):
    """Brute force keeps fp32 vectors (the match operand) even with the
    int8 store; the service must still rerank through the knob's store and
    agree with the facade exactly."""
    v = jnp.asarray(small_corpus[:256])
    ann = AnnIndex.build(v, BruteForceConfig(), rerank_store="int8",
                         use_kernel=False)
    assert ann.index.vectors is not None and ann.quantized_rerank
    svc = AnnService(ann, AnnServiceConfig(
        k=10, depth=50, rerank=True, max_batch=8, use_kernel=False))
    s_srv, i_srv = svc.search_batch(small_corpus[:8])
    s_d, i_d = ann.search(jnp.asarray(small_corpus[:8]), k=10, depth=50,
                          rerank=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_d), i_srv)
    np.testing.assert_array_equal(np.asarray(s_d), s_srv)


def test_quantize_store_reconstruction_is_symmetric(small_corpus):
    v = bruteforce.l2_normalize(jnp.asarray(small_corpus[:128]))
    vq = builder.quantize_store(v)
    v_hat = np.asarray(vq.q, np.float32) * np.asarray(vq.scale)[:, None]
    # per-component reconstruction within half a step; zero maps to zero
    assert (np.abs(v_hat - np.asarray(v)) <= np.asarray(vq.scale)[:, None] / 2 + 1e-6).all()
    z = builder.quantize_store(jnp.zeros((3, 8), jnp.float32))
    assert (np.asarray(z.q) == 0).all()


# -- AnnService result cache -------------------------------------------------


def test_ann_service_result_cache_hits_and_counters(small_corpus):
    v = jnp.asarray(small_corpus[:512])
    ann = AnnIndex.build(v, FakeWordsConfig(quantization=50), use_kernel=False)
    svc = AnnService(ann, AnnServiceConfig(
        k=10, depth=50, rerank=True, max_batch=8, cache_size=4))
    qs = small_corpus[:8]
    s0, i0 = svc.search_batch(qs)
    assert svc.stats()["cache_misses"] == 1 and svc.stats()["cache_hits"] == 0
    s1, i1 = svc.search_batch(qs)  # identical batch -> pure cache hit
    assert svc.stats()["cache_hits"] == 1
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(s0, s1)
    # distinct queries miss; LRU stays bounded at cache_size
    for j in range(6):
        svc.search_batch(small_corpus[8 * (j + 1): 8 * (j + 2)])
    st = svc.stats()
    assert st["cache_misses"] == 7 and st["cache_entries"] <= 4
    # cached results equal uncached results (cache off)
    svc_off = AnnService(ann, AnnServiceConfig(
        k=10, depth=50, rerank=True, max_batch=8))
    s2, i2 = svc_off.search_batch(qs)
    np.testing.assert_array_equal(i1, i2)
    assert svc_off.stats()["cache_entries"] == 0


def test_ann_service_cache_respects_rerank_on_rep_collisions(small_corpus):
    """Two distinct raw queries can share a quantized tf row; with rerank on
    the cache must NOT serve one query's exact scores for the other."""
    v = jnp.asarray(small_corpus[:256])
    ann = AnnIndex.build(v, FakeWordsConfig(quantization=2), use_kernel=False)
    svc = AnnService(ann, AnnServiceConfig(
        k=5, depth=50, rerank=True, max_batch=4, cache_size=8))
    qa = small_corpus[:4]
    qb = qa + 1e-4  # same tf row at Q=2, different exact cosine
    ra = fakewords.encode_queries(jnp.asarray(qa), ann.config)
    rb = fakewords.encode_queries(jnp.asarray(qb), ann.config)
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    s_a, _ = svc.search_batch(qa)
    s_b, _ = svc.search_batch(qb)
    assert svc.stats()["cache_hits"] == 0  # rep collided, raw queries didn't
    assert not np.array_equal(s_a, s_b)
