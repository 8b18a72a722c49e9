"""The quantized read path (docs/DESIGN.md §12): int8/int4 primary postings
with dequant fused into the score stage.

Covers kernel==XLA bit-parity per encoding, the int4 per-element dequant
error bound (hypothesis + deterministic fallback), recall@10 within 0.02 of
fp32 through the served read path (kernel AND XLA), segmented-vs-monolithic
bitwise parity for quantized stores (the PR's IndexWriter fix), blockmax
beta=1.0 pruned-vs-full parity on dequantized bounds, save/load
round-trips, the memory-budget planner, and sharded int4 parity (8 fake
host devices, subprocess — same pattern as tests/test_distributed.py).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, builder, eval as ev
from repro.core import memory_budget as mb
from repro.core.index import AnnIndex
from repro.core.segments import IndexWriter, SegmentedAnnIndex
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Encodings with a quantized primary-postings store.  LSH/kd-tree have
# none (signature/reduced-point stores) and must refuse loudly.
QUANT_CONFIGS = [
    FakeWordsConfig(quantization=50),
    FakeWordsConfig(quantization=50, scoring="dot"),
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


# -- fused kernel == XLA reference, per encoding x bit width -----------------


@pytest.mark.parametrize("pp", ["int8", "int4"])
@pytest.mark.parametrize("cfg", QUANT_CONFIGS, ids=_ids)
def test_quantized_kernel_matches_xla(small_corpus, cfg, pp):
    """The Pallas fused-dequant score stage (interpret mode on CPU) must
    return the exact ids and allclose scores of the XLA reference."""
    v = jnp.asarray(small_corpus[:512])
    q = jnp.asarray(small_corpus[:8])
    ann = AnnIndex.build(v, cfg, rerank_store="none", primary_postings=pp)
    assert ann.index.pq is not None or (
        isinstance(cfg, FakeWordsConfig) and cfg.scoring == "dot"
        and pp == "int8"  # dot-int8 IS the native int8 tf: no pq leaf
    )
    s_k, i_k = ann.search(q, k=10, depth=50, use_kernel=True)
    s_x, i_x = ann.search(q, k=10, depth=50, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_x))
    np.testing.assert_allclose(
        np.asarray(s_k), np.asarray(s_x), rtol=1e-5, atol=1e-5
    )


def test_unquantizable_encodings_refuse():
    v = jnp.asarray(np.random.default_rng(13).normal(size=(64, 32)).astype(np.float32))
    for cfg in (LexicalLshConfig(buckets=64, hashes=2),
                KdTreeConfig(dims=8, backend="scan")):
        with pytest.raises((ValueError, NotImplementedError)):
            AnnIndex.build(v, cfg, primary_postings="int8")


# -- int4 per-element dequant error bound ------------------------------------


def _check_int4_error_bound(n, t, group, seed):
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n, t)).astype(np.float32) * rng.uniform(
        0.01, 10.0, size=(n, 1)
    ).astype(np.float32)
    pq = builder.quantize_postings(jnp.asarray(m), bits=4, group=group)
    deq = np.asarray(builder.dequantize_postings(pq, jnp.float32))
    # Per-element |v - deq| <= group_scale/2: round-to-nearest with step
    # ``scale`` over a range the scale covers by construction.
    tg = ((t + group - 1) // group) * group
    scales = np.asarray(pq.scale)  # (n, tg/group)
    per_col = np.repeat(scales, group, axis=1)[:, :t]
    err = np.abs(m - deq)
    assert (err <= per_col / 2 + 1e-6).all(), float((err - per_col / 2).max())


def test_int4_dequant_error_bound_deterministic():
    for seed in range(8):
        _check_int4_error_bound(4 + 3 * seed, 5 + 11 * seed, 32 if seed % 2 else 64, seed)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        st.integers(2, 24), st.integers(2, 90),
        st.sampled_from([32, 64]), st.integers(0, 2**31 - 1),
    )
    def test_int4_dequant_error_bounded_by_half_group_scale(n, t, group, seed):
        _check_int4_error_bound(n, t, group, seed)
except ImportError:  # pragma: no cover - hypothesis is a dev dependency
    pass


# -- recall@10 within 0.02 of fp32 through the served read path --------------


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("cfg", QUANT_CONFIGS, ids=_ids)
def test_quantized_recall_within_002_of_fp32(cfg, use_kernel):
    """int8/int4 postings with the frontier-paired int8 rerank must stay
    within 0.02 recall@10 of the fp32 postings serving the same rerank
    (the store the memory-budget planner actually pairs them with).  Data
    is drawn in-test: the shared ``rng`` fixture is stateful across the
    suite and a recall property this tight must not move with test order."""
    rng = np.random.default_rng(7)
    corpus = rng.normal(size=(1024, 64)).astype(np.float32)
    corpus += 0.5 * rng.normal(size=(1, 64)).astype(np.float32)
    v = jnp.asarray(corpus)
    q = jnp.asarray(corpus[:32] + 0.01 * rng.normal(size=(32, 64))
                    .astype(np.float32))
    _, gt = bruteforce.exact_topk(v, q, 10, use_kernel=False)
    recalls = {}
    for pp in ("fp32", "int8", "int4"):
        ann = AnnIndex.build(v, cfg, rerank_store="int8", primary_postings=pp)
        _, ids = ann.search(q, k=10, depth=150, rerank=True,
                            use_kernel=use_kernel)
        recalls[pp] = float(ev.recall_at(gt, ids))
    assert recalls["fp32"] - recalls["int8"] <= 0.02, recalls
    assert recalls["fp32"] - recalls["int4"] <= 0.02, recalls


# -- segmented quantized builds: bitwise == monolithic (IndexWriter fix) -----


@pytest.mark.parametrize(
    "cfg,pp",
    [
        (FakeWordsConfig(quantization=50), "int8"),
        (FakeWordsConfig(quantization=50), "int4"),
        (FakeWordsConfig(quantization=50, scoring="dot"), "int4"),
        (BruteForceConfig(), "int8"),
    ],
    ids=["classic-int8", "classic-int4", "dot-int4", "bruteforce-int8"],
)
def test_segmented_quantized_bitwise_equals_monolithic(small_corpus, cfg, pp, tmp_path):
    """A flushed + merged segmented index with the int8 rerank store and
    quantized postings must search bitwise-identically to a monolithic
    build of the same rows — the writer's store choice now plumbs through
    to the BuildPipeline and merges rebuild from the source sidecar."""
    v = small_corpus[:240]
    q = jnp.asarray(small_corpus[:7])
    mono = AnnIndex.build(jnp.asarray(v), cfg, rerank_store="int8",
                          primary_postings=pp)
    w = IndexWriter(cfg, rerank_store="int8", primary_postings=pp)
    w.add(v[:100])
    w.flush()
    w.add(v[100:])
    w.flush()
    w._merge_range(0, 2)
    reader = w.refresh()
    s_m, i_m = mono.search(q, k=10, depth=60, rerank=True)
    s_r, i_r = reader.search(q, k=10, depth=60, rerank=True)
    np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(s_m), np.asarray(s_r))
    # Commit persists the source sidecar (vectors were dropped); reload
    # serves identically and the reopened writer can keep merging.
    path = str(tmp_path / "idx")
    w.path = path
    w.commit()
    assert os.path.exists(os.path.join(path, w._segments[0].name, "source.npz"))
    r2 = SegmentedAnnIndex.load(path)
    s_2, i_2 = r2.search(q, k=10, depth=60, rerank=True)
    np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_2))
    np.testing.assert_array_equal(np.asarray(s_m), np.asarray(s_2))
    w2 = IndexWriter.open(path)
    assert w2.rerank_store == "int8" and w2.primary_postings == pp


def test_writer_rejects_unknown_rerank_store():
    with pytest.raises(ValueError):
        IndexWriter(FakeWordsConfig(quantization=50), rerank_store="fp16")


# -- blockmax on dequantized bounds: beta=1.0 parity (satellite 6) -----------


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("pp", ["int8", "int4"])
@pytest.mark.parametrize("scoring", ["classic", "dot"])
def test_blockmax_quantized_beta1_parity(small_corpus, scoring, pp, use_kernel):
    """Keeping every block must reproduce the dense quantized search
    exactly: the block upper bounds are maxima over DEQUANTIZED values, so
    no true candidate can be pruned at beta=1.0."""
    from repro.core import blockmax

    cfg = FakeWordsConfig(quantization=50, scoring=scoring)
    v = jnp.asarray(small_corpus[:512])
    q = jnp.asarray(small_corpus[:6])
    ann = AnnIndex.build(v, cfg, rerank_store="none", primary_postings=pp)
    bm = blockmax.build_blockmax(ann.index, block_size=64)
    if ann.index.pq is not None:
        # Dequantized f32 bounds; dot-int8 has no pq leaf (native int8 tf)
        # and keeps the exact integer bound path.
        assert jnp.issubdtype(bm.ub.dtype, jnp.floating)
    s_full, i_full = ann.search(q, k=10, depth=50, use_kernel=use_kernel)
    q_tf = ann.encode_queries(bruteforce.l2_normalize(q))
    s_pr, i_pr = blockmax.pruned_search(
        ann.index, bm, q_tf, n_keep=bm.ub.shape[0], depth=50,
        use_kernel=use_kernel,
    )
    np.testing.assert_array_equal(np.asarray(i_full), np.asarray(i_pr[:, :10]))
    np.testing.assert_allclose(
        np.asarray(s_full), np.asarray(s_pr[:, :10]), rtol=1e-5, atol=1e-5
    )


# -- persistence -------------------------------------------------------------


@pytest.mark.parametrize("pp", ["int8", "int4"])
@pytest.mark.parametrize("cfg", QUANT_CONFIGS, ids=_ids)
def test_quantized_save_load_bit_for_bit(small_corpus, cfg, pp, tmp_path):
    v = jnp.asarray(small_corpus[:256])
    q = jnp.asarray(small_corpus[:5])
    ann = AnnIndex.build(v, cfg, rerank_store="int8", primary_postings=pp)
    ann.save(str(tmp_path / "idx"))
    back = AnnIndex.load(str(tmp_path / "idx"))
    if ann.index.pq is not None:
        np.testing.assert_array_equal(
            np.asarray(ann.index.pq.q), np.asarray(back.index.pq.q))
        np.testing.assert_array_equal(
            np.asarray(ann.index.pq.scale), np.asarray(back.index.pq.scale))
        assert (back.index.pq.bits, back.index.pq.group, back.index.pq.cols) \
            == (ann.index.pq.bits, ann.index.pq.group, ann.index.pq.cols)
    s0, i0 = ann.search(q, k=10, depth=40, rerank=True)
    s1, i1 = back.search(q, k=10, depth=40, rerank=True)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


# -- memory-budget planner ---------------------------------------------------


def test_budget_planner_walks_the_frontier():
    cfg = FakeWordsConfig(quantization=50)
    n, d = 2000, 64
    huge = mb.plan_for_budget(cfg, n, d, 10**12)
    assert (huge["primary_postings"], huge["rerank_store"]) == ("fp32", "exact")
    picks = []
    for budget in (10**12, 900_000, 600_000, 450_000):
        p = mb.plan_for_budget(cfg, n, d, budget)
        assert p["estimated_bytes"] <= budget
        picks.append((p["primary_postings"], p["rerank_store"]))
    # Monotone walk down the frontier as the budget shrinks.
    order = [(e["primary_postings"], e["rerank_store"])
             for e in mb.DEFAULT_FRONTIER]
    assert [order.index(p) for p in picks] == sorted(
        order.index(p) for p in picks)
    with pytest.raises(ValueError):
        mb.plan_for_budget(cfg, n, d, 1000)


def test_budget_planner_pins_caller_knobs():
    cfg = BruteForceConfig()
    p = mb.plan_for_budget(cfg, 1000, 64, 10**12, primary_postings="int4")
    assert p["primary_postings"] == "int4"
    p = mb.plan_for_budget(cfg, 1000, 64, 10**12, rerank_store="none")
    assert p["rerank_store"] == "none"


def test_budget_estimate_matches_actual_store(small_corpus):
    """The analytic per-doc byte formula must track what the builder
    actually materializes (within the replicated-statistics epsilon)."""
    v = jnp.asarray(small_corpus[:512])
    for cfg in QUANT_CONFIGS:
        for pp, rs in (("int8", "none"), ("int4", "int8")):
            ann = AnnIndex.build(v, cfg, rerank_store=rs, primary_postings=pp)
            est = mb.estimate_bytes(cfg, 512, 64, pp, rs)
            actual = ann.nbytes()
            assert est <= actual  # estimate excludes O(T) statistics
            assert actual - est <= 64 * 64 * 8, (cfg, pp, rs, est, actual)


def test_build_with_memory_budget_picks_and_serves(small_corpus):
    cfg = FakeWordsConfig(quantization=50)
    v = jnp.asarray(small_corpus[:1000])
    ann = AnnIndex.build(v, cfg, memory_budget_bytes=300_000)
    assert ann.index.pq is not None  # budget forced a quantized store
    s, i = ann.search(jnp.asarray(small_corpus[:4]), k=10, depth=50)
    assert np.asarray(i).shape == (4, 10)


def test_load_frontier_orders_by_measured_recall(tmp_path):
    import json

    bench = {"quantized_ab": [
        {"postings": "int4", "recall_at_10": 0.99},
        {"postings": "fp32", "recall_at_10": 0.95},
        {"postings": "int8", "recall_at_10": 0.97},
    ]}
    p = tmp_path / "BENCH_6.json"
    p.write_text(json.dumps(bench))
    frontier = mb.load_frontier(str(p))
    assert frontier[0]["primary_postings"] == "int4"
    # every default entry survives (rerank/pruning variants keep analytic order)
    assert len(frontier) == len(mb.DEFAULT_FRONTIER)


# -- sharded int4 parity (multihost-sim job) ---------------------------------


def test_sharded_int4_build_and_search_parity():
    """8 fake host devices: the sharded int4 build must equal the local
    build bit-for-bit (row-local grouped scales shard freely) and the
    sharded search must return the local ids/scores."""
    run_subprocess(
        """
        from repro.core import distributed
        from repro.core.index import AnnIndex
        from repro.core.types import FakeWordsConfig

        rng = np.random.default_rng(13)
        V = rng.normal(size=(512, 64)).astype(np.float32)
        Q = rng.normal(size=(8, 64)).astype(np.float32)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("doc",))
        cfg = FakeWordsConfig(quantization=50)
        local = AnnIndex.build(jnp.asarray(V), cfg, rerank_store="int8",
                               primary_postings="int4")
        idx = distributed.build_sharded(
            mesh, jnp.asarray(V), cfg, ("doc",), rerank_store="int8",
            primary_postings="int4")
        np.testing.assert_array_equal(
            np.asarray(local.index.pq.q), np.asarray(idx.pq.q))
        np.testing.assert_array_equal(
            np.asarray(local.index.pq.scale), np.asarray(idx.pq.scale))
        fn = distributed.make_sharded_search(
            mesh, cfg, ("doc",), k=10, depth=512, rerank=True,
            rerank_store="int8", postings_bits=4)
        from repro.core import bruteforce
        q = bruteforce.l2_normalize(jnp.asarray(Q))
        ann = AnnIndex(config=cfg, index=idx)
        q_rep = ann.pipeline.encoder(idx, q)
        s, i = fn(idx, q_rep, q)
        ls, li = local.search(jnp.asarray(Q), k=10, depth=512, rerank=True)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(li))
        np.testing.assert_allclose(np.asarray(s), np.asarray(ls),
                                   rtol=1e-5, atol=1e-5)
        print("SHARDED-INT4-OK")
        """
    )
