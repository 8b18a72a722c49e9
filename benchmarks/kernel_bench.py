"""Kernel micro-bench: wall-clock of jnp reference paths on CPU (relative
numbers; the Pallas kernels target TPU and are validated in interpret mode —
timing interpret mode is meaningless, so off-TPU the fused rows time the XLA
online-reduction reference and report bytes/flops per call for the roofline
narrative).

The fused-vs-unfused section quantifies the HBM-traffic win of the fused
streaming score->top-k kernel (docs/DESIGN.md §4): unfused search writes and
re-reads a (B, N) f32 score matrix; fused search streams the index once and
emits only O(B * depth) — its ``stream_mb`` EXCLUDES the score matrix by
construction.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockmax, bruteforce, fakewords, lexical_lsh
from repro.core.index import AnnIndex
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
)
from repro.kernels.fused_topk import ops as fused_ops
from repro.kernels.fused_topk import ref as fused_ref


def _time(f, *args, n=5) -> float:
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


def fused_vs_unfused(
    n_docs: int, dim: int, batch: int, depth: int = 100
) -> Tuple[List[Dict], Dict]:
    """Fused streaming top-k vs unfused score-matrix + top_k, both scoring
    modes.  Returns (rows, summary).  Off-TPU the fused timing uses the XLA
    streaming reference (same memory behavior, timeable); on TPU it is the
    Pallas kernel itself."""
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    on_tpu = jax.default_backend() == "tpu"
    rows: List[Dict] = []
    summary: Dict = {"depth": depth, "on_tpu": on_tpu}

    for scoring in ("classic", "dot"):
        cfg = FakeWordsConfig(quantization=50, scoring=scoring)
        idx = fakewords.build(vecs, cfg)
        q_tf = fakewords.encode_queries(vecs[:batch], cfg)
        docs = idx.scored if scoring == "classic" else idx.tf
        if scoring == "classic":
            qv = fakewords.classic_query(idx, q_tf)
        else:
            qv = fakewords.dot_query(idx, q_tf, dtype=jnp.int8)

        # unfused: dense (B, N) f32 scores written + re-read by top_k
        unfused = jax.jit(
            lambda q, d: jax.lax.top_k(fused_ref.scores_ref(q, d), depth)
        )
        dt_un = _time(unfused, qv, docs)
        score_matrix = batch * n_docs * 4 * 2  # write + top_k read-back
        un_mb = (_nbytes(docs, qv) + score_matrix) / 1e6
        rows.append({
            "kernel": f"search({scoring}) unfused einsum+top_k",
            "us_per_call": dt_un * 1e6, "stream_mb": un_mb,
        })

        # fused: index stream + O(B*depth) result; NO (B, N) matrix
        if on_tpu:
            fused_f = jax.jit(
                lambda q, d: fused_ops.fused_topk(q, d, depth)
            )
            impl = "pallas"
        else:
            fused_f = jax.jit(
                lambda q, d: fused_ref.streaming_topk_ref(q, d, depth)
            )
            impl = "xla-stream"
        dt_f = _time(fused_f, qv, docs)
        f_mb = (_nbytes(docs, qv) + batch * depth * (4 + 4)) / 1e6
        rows.append({
            "kernel": f"search({scoring}) fused top-k [{impl}]",
            "us_per_call": dt_f * 1e6, "stream_mb": f_mb,
        })
        # Measured regression check: the streamed path must retrieve the
        # same ids as the unfused oracle (the analytic byte formulas above
        # cannot fail; this can).
        _, i_un = unfused(qv, docs)
        _, i_f = fused_f(qv, docs)
        summary[scoring] = {
            "unfused_mb": un_mb, "fused_mb": f_mb,
            "stream_cut": un_mb / f_mb,
            "speedup": dt_un / dt_f,
            "ids_match": bool((np.asarray(i_un) == np.asarray(i_f)).all()),
        }
    return rows, summary


def pruned_vs_full(
    n_docs: int, dim: int, batch: int = 8, depth: int = 100,
    beta: float = 0.1, block_size: int = 256,
) -> Tuple[List[Dict], Dict]:
    """Blockmax two-stage pruning vs the full scan, all three scoring modes
    (classic / dot-int8 / LSH).  Off-TPU both sides time their XLA reference
    realizations; on TPU they route through the fused kernels.

    Byte accounting is per batch: the full scan streams the whole stored
    matrix once per batch; the pruned path streams the block upper bounds
    plus each query's gathered kept-block rows (B * n_keep * block_size).
    Pruning therefore wins bytes when batch * beta < 1 — the low-QPS
    latency-sensitive serving regime the paper's filtering targets — and
    wins compute (the stage-2 GEMM is a beta-fraction of the work) broadly.
    """
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    vecs = bruteforce.l2_normalize(vecs)
    on_tpu = jax.default_backend() == "tpu"
    uk = None if on_tpu else False  # Pallas on TPU; timeable XLA ref on CPU
    n_keep = max(1, int(beta * -(-n_docs // block_size)))
    rows: List[Dict] = []
    summary: Dict = {
        "depth": depth, "beta": beta, "n_keep": n_keep, "on_tpu": on_tpu,
    }

    def add(mode: str, full_fn, full_mb: float, pruned_fn, pruned_mb: float):
        dt_full = _time(full_fn)
        dt_pr = _time(pruned_fn)
        rows.append({
            "kernel": f"search({mode}) full scan",
            "us_per_call": dt_full * 1e6, "stream_mb": full_mb,
        })
        rows.append({
            "kernel": f"search({mode}) blockmax beta={beta}",
            "us_per_call": dt_pr * 1e6, "stream_mb": pruned_mb,
        })
        summary[mode] = {
            "full_mb": full_mb, "pruned_mb": pruned_mb,
            "byte_cut": full_mb / pruned_mb, "speedup": dt_full / dt_pr,
        }

    for scoring in ("classic", "dot"):
        cfg = FakeWordsConfig(quantization=50, scoring=scoring)
        idx = fakewords.build(vecs, cfg, normalized=True)
        q_tf = fakewords.encode_queries(vecs[:batch], cfg, normalized=True)
        bm = blockmax.build_blockmax(idx, block_size)
        mat = idx.scored if scoring == "classic" else idx.tf
        add(
            scoring,
            lambda i=idx, q=q_tf, s=scoring: fakewords.search(
                i, q, None, k=depth, depth=depth, scoring=s, use_kernel=uk),
            (_nbytes(mat, q_tf) + batch * depth * 8) / 1e6,
            lambda i=idx, b=bm, q=q_tf: blockmax.pruned_search(
                i, b, q, n_keep=n_keep, depth=depth, use_kernel=uk),
            (_nbytes(bm.ub, q_tf)
             + batch * n_keep * block_size * mat.shape[1] * mat.dtype.itemsize
             + batch * depth * 8) / 1e6,
        )

    lcfg = LexicalLshConfig(buckets=300, hashes=1)
    lidx = lexical_lsh.build(vecs, lcfg, normalized=True)
    sig_q = lexical_lsh.encode(vecs[:batch], lcfg)
    bm_l = blockmax.build_blockmax(lidx, block_size)
    add(
        "lsh",
        lambda: lexical_lsh.search(
            lidx, sig_q, None, k=depth, depth=depth, use_kernel=uk),
        (_nbytes(lidx.sig, sig_q) + batch * depth * 8) / 1e6,
        lambda: blockmax.pruned_search(
            lidx, bm_l, sig_q, n_keep=n_keep, depth=depth, use_kernel=uk),
        (_nbytes(bm_l.ub, sig_q)
         + batch * n_keep * block_size * lidx.sig.shape[1] * 4
         + batch * depth * 8) / 1e6,
    )
    return rows, summary


def pipeline_latency(
    n_docs: int, dim: int, batch: int, depth: int = 100, k: int = 10
) -> List[Dict]:
    """End-to-end latency rows for every encoding through the shared staged
    SearchPipeline (AnnIndex.search: encode -> match -> exact rerank) — the
    same code path the serving layer runs.  Off-TPU the match stage times
    the XLA reference; on TPU the fused Pallas kernel."""
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    queries = vecs[:batch]
    uk = None if jax.default_backend() == "tpu" else False
    rows: List[Dict] = []
    for cfg in (
        FakeWordsConfig(quantization=50),
        FakeWordsConfig(quantization=50, scoring="dot"),
        LexicalLshConfig(buckets=300, hashes=1),
        KdTreeConfig(dims=8, backend="scan"),
        BruteForceConfig(),
    ):
        ann = AnnIndex.build(vecs, cfg, use_kernel=uk)
        tag = ann.method
        if isinstance(cfg, FakeWordsConfig):
            tag = f"{ann.method}/{cfg.scoring}"
        dt = _time(lambda a=ann, q=queries: a.search(q, k=k, depth=depth, rerank=True))
        rows.append({
            "kernel": f"pipeline({tag}) encode+match+rerank",
            "us_per_call": dt * 1e6,
            "index_mb": ann.nbytes() / 1e6,
        })
    return rows


def build_bench(n_docs: int, dim: int) -> List[Dict]:
    """Build-time rows, local vs mesh-sharded, for every encoding through
    the staged BuildPipeline (docs/DESIGN.md §8).  The sharded build runs
    the SAME stages row-parallel under ``shard_map`` over every available
    device (1 device still exercises the psum path)."""
    from repro.core import builder, distributed

    rng = np.random.default_rng(0)
    n_dev = len(jax.devices())
    n_docs -= n_docs % n_dev  # divisibility for the doc shards
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    mesh = distributed.make_mesh((n_dev,), ("data",))
    rows: List[Dict] = []
    for cfg in (
        FakeWordsConfig(quantization=50),
        LexicalLshConfig(buckets=300, hashes=1),
        KdTreeConfig(dims=8, backend="scan"),
        BruteForceConfig(),
    ):
        tag = type(cfg).__name__.replace("Config", "")
        bp = builder.make_build_pipeline(cfg)
        # Jit BOTH sides so the rows compare steady-state compiled builds
        # (_time's warmup call pays each compile); an eager local build
        # would otherwise lose on per-op dispatch, not on sharding.
        local_fn = jax.jit(bp.build_local)
        sharded_fn = jax.jit(bp.sharded_build_fn(mesh, ("data",), n_docs))

        def local(fn=local_fn):
            idx = fn(vecs)
            jax.block_until_ready(jax.tree_util.tree_leaves(idx))
            return idx

        def sharded(fn=sharded_fn):
            idx = fn(vecs)
            jax.block_until_ready(jax.tree_util.tree_leaves(idx))
            return idx

        dt_l = _time(local, n=2)
        dt_s = _time(sharded, n=2)
        rows.append({
            "kernel": f"build({tag}) local", "us_per_call": dt_l * 1e6,
            "docs_per_s": n_docs / dt_l,
        })
        rows.append({
            "kernel": f"build({tag}) sharded x{n_dev}",
            "us_per_call": dt_s * 1e6, "docs_per_s": n_docs / dt_s,
        })
    return rows


def rerank_bench(
    n_docs: int, dim: int, batch: int, depth: int = 100, k: int = 10
) -> Tuple[List[Dict], Dict]:
    """fp32 vs int8 rerank store: latency, gather bytes, recall@10 against
    the exact oracle.  The int8 gather moves ~(4 dim)/(dim + 4) ~= 4x fewer
    bytes per candidate (docs/DESIGN.md §8); the measured recall delta is
    the price, bounded by the ||q||_1 * scale/2 score-error bound."""
    from repro.core import eval as ev

    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    queries = vecs[:batch] + 0.01 * jnp.asarray(
        rng.normal(size=(batch, dim)).astype(np.float32))
    uk = None if jax.default_backend() == "tpu" else False
    _, gt = bruteforce.exact_topk(vecs, queries, k, use_kernel=uk)
    cfg = FakeWordsConfig(quantization=50)
    rows: List[Dict] = []
    summary: Dict = {"depth": depth}
    for store in ("exact", "int8"):
        ann = AnnIndex.build(vecs, cfg, rerank_store=store, use_kernel=uk)
        dt = _time(lambda a=ann: a.search(queries, k=k, depth=depth, rerank=True))
        _, ids = ann.search(queries, k=k, depth=depth, rerank=True)
        recall = float(ev.recall_at(gt, ids))
        # Gather bytes per batch: depth candidate rows per query.
        per_row = dim * 4 if store == "exact" else dim + 4
        gather_mb = batch * depth * per_row / 1e6
        rows.append({
            "kernel": f"rerank({store}) gather+cosine+topk",
            "us_per_call": dt * 1e6, "gather_mb": gather_mb,
            "recall_at_10": recall,
        })
        summary[store] = {"gather_mb": gather_mb, "recall": recall,
                          "us": dt * 1e6}
    summary["byte_cut"] = summary["exact"]["gather_mb"] / summary["int8"]["gather_mb"]
    summary["recall_delta"] = summary["exact"]["recall"] - summary["int8"]["recall"]
    return rows, summary


def segments_bench(
    n_docs: int, dim: int, batch: int, depth: int = 100, k: int = 10,
) -> Tuple[List[Dict], Dict]:
    """Segmented (Lucene-lifecycle) serving cost (docs/DESIGN.md §11):
    search latency at 1 / 4 / 16 segments over the same corpus, full-merge
    wall time from 16 segments, and post-merge recall@10 (which must equal
    the 1-segment recall — the merge rebuilds through the same
    BuildPipeline).  The latency spread IS the price of segment fan-out
    (per-segment dispatch + merge) that a background merge policy buys
    back."""
    from repro.core import eval as ev
    from repro.core.segments import IndexWriter
    from repro.core.types import FakeWordsConfig as FWC

    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n_docs, dim)).astype(np.float32)
    queries = jnp.asarray(vecs[:batch])
    uk = None if jax.default_backend() == "tpu" else False
    _, gt = bruteforce.exact_topk(jnp.asarray(vecs), queries, k, use_kernel=uk)
    cfg = FWC(quantization=50)
    rows: List[Dict] = []
    summary: Dict = {"depth": depth}
    w = None
    for n_seg in (1, 4, 16):
        # One writer at a time: each holds a full index copy (originals +
        # tf/scored), and only the last (16-segment) one feeds the merge
        # timing below.
        w = IndexWriter(cfg, use_kernel=uk, merge_policy=None)
        for chunk in np.array_split(vecs, n_seg):
            w.add(chunk)
            w.flush()
        reader = w.refresh()

        def search(r=reader):
            return r.search(queries, k=k, depth=depth, rerank=True)

        dt = _time(search)
        _, ids = search()
        recall = float(ev.recall_at(gt, jnp.asarray(np.asarray(ids))))
        rows.append({
            "kernel": f"segments({n_seg}) search encode+match+merge+rerank",
            "us_per_call": dt * 1e6, "recall_at_10": recall,
        })
        summary[n_seg] = {"us": dt * 1e6, "recall": recall}
    t0 = time.perf_counter()
    w.force_merge(1)
    merged = w.refresh()
    merge_s = time.perf_counter() - t0
    _, ids = merged.search(queries, k=k, depth=depth, rerank=True)
    post_recall = float(ev.recall_at(gt, jnp.asarray(np.asarray(ids))))
    rows.append({
        "kernel": "segments merge 16->1", "us_per_call": merge_s * 1e6,
        "recall_at_10": post_recall,
    })
    summary["merge_s"] = merge_s
    summary["post_merge_recall"] = post_recall
    summary["fanout_cost"] = summary[16]["us"] / summary[1]["us"]
    return rows, summary


def quantized_ab(
    n_docs: int, dim: int, batch: int, depth: int = 100, k: int = 10,
    group: int = 32, n_calls: int = 20,
) -> Tuple[List[Dict], Dict]:
    """fp32 vs int8 vs int4 primary postings A/B (docs/DESIGN.md §12):
    build wall time, match-only QPS and p50/p99 latency, recall@10 against
    the exact oracle, and match-stage bytes streamed per full scan.

    Two method families: the cosine path (FlatIndex; a genuine 4-byte/elem
    fp32 baseline, so the byte cuts are the headline 4x / 6x numbers) and
    fake-words classic (whose fp32 store is the bf16 ``scored`` matrix plus
    the int8 tf).  Every row serves the full read path the budget planner
    pairs with a quantized store — match at ``depth`` candidates, rerank
    through the SAME int8 store — so ``recall_at_10`` isolates the match
    encoding (the rerank cost is constant across rows) and
    ``match_recall_at_10`` keeps the raw pre-rerank stage number.  Byte
    accounting reuses
    :func:`repro.core.memory_budget.postings_bytes_per_doc` so the A/B rows
    and the budget planner can never disagree.  The acceptance bars — int8
    >= 3.5x fewer match bytes within 0.02 recall of fp32, int4 >= 6x within
    0.05 — are recorded per row as ``bytes_cut_vs_fp32`` /
    ``recall_delta_vs_fp32`` on the cosine family."""
    from repro.core import eval as ev
    from repro.core import memory_budget as mb

    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    queries = vecs[:batch] + 0.01 * jnp.asarray(
        rng.normal(size=(batch, dim)).astype(np.float32))
    uk = None if jax.default_backend() == "tpu" else False
    _, gt = bruteforce.exact_topk(vecs, queries, k, use_kernel=uk)
    rows: List[Dict] = []
    summary: Dict = {"depth": depth, "group": group, "k": k}
    for cfg in (BruteForceConfig(), FakeWordsConfig(quantization=50)):
        base: Dict = {}
        for pp in ("fp32", "int8", "int4"):
            t0 = time.perf_counter()
            ann = AnnIndex.build(
                vecs, cfg, rerank_store="int8", primary_postings=pp,
                postings_group=group, use_kernel=uk,
            )
            jax.block_until_ready(jax.tree_util.tree_leaves(ann.index))
            build_s = time.perf_counter() - t0

            def search(a=ann, rerank=True):
                return a.search(queries, k=k, depth=depth, rerank=rerank)

            jax.block_until_ready(search())  # compile
            lat = []
            for _ in range(n_calls):
                t1 = time.perf_counter()
                jax.block_until_ready(search())
                lat.append(time.perf_counter() - t1)
            lat_ms = np.asarray(lat, np.float64) * 1e3
            _, ids = search()
            recall = float(ev.recall_at(gt, ids))
            _, ids_m = search(rerank=False)
            match_recall = float(ev.recall_at(gt, ids_m))
            match_mb = (
                n_docs * mb.postings_bytes_per_doc(cfg, dim, pp, group) / 1e6
            )
            row = {
                "method": ann.method,
                "postings": pp,
                "build_s": round(build_s, 3),
                "qps": round(batch / float(np.percentile(lat_ms, 50)) * 1e3, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "recall_at_10": round(recall, 4),
                "match_recall_at_10": round(match_recall, 4),
                "match_mb": round(match_mb, 3),
            }
            if pp == "fp32":
                base = {"mb": match_mb, "recall": recall}
            row["bytes_cut_vs_fp32"] = round(base["mb"] / match_mb, 2)
            row["recall_delta_vs_fp32"] = round(base["recall"] - recall, 4)
            rows.append(row)
            summary.setdefault(ann.method, {})[pp] = {
                "bytes_cut": row["bytes_cut_vs_fp32"],
                "recall_delta": row["recall_delta_vs_fp32"],
            }
    return rows, summary


def filtered_ab(
    n_docs: int, dim: int, batch: int, depth: int = 100, k: int = 10,
    ratios: Tuple[float, ...] = (0.01, 0.1, 0.5), n_calls: int = 20,
) -> Tuple[List[Dict], Dict]:
    """Filtered vs unfiltered serving A/B (docs/DESIGN.md §13): QPS,
    p50/p99 latency, and recall@10 at 1% / 10% / 50% selectivity for the
    classic fake-words path over fp32 / int8 / int4 primary postings.

    The filter is applied INSIDE the match stage (one kernel pass — the
    bitmap operand masks scores to -inf in the tile loop), so filtered
    latency must track unfiltered latency, not the depth-inflated
    post-filter cost.  Recall is scored against the exact oracle over the
    kept sub-corpus (mapped back to global ids), so every tier's number is
    a true filtered recall, comparable across selectivities."""
    from repro.core import eval as ev

    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    queries = vecs[:batch] + 0.01 * jnp.asarray(
        rng.normal(size=(batch, dim)).astype(np.float32))
    uk = None if jax.default_backend() == "tpu" else False
    rows: List[Dict] = []
    summary: Dict = {"depth": depth, "k": k, "ratios": list(ratios)}

    def truth_under(mask: np.ndarray) -> jax.Array:
        kept = np.flatnonzero(mask)
        _, gi = bruteforce.exact_topk(vecs[kept], queries, k, use_kernel=uk)
        return jnp.asarray(kept[np.asarray(gi)])

    masks = {}
    for ratio in ratios:
        m = (np.random.default_rng(int(ratio * 1000) + 7).random(n_docs)
             < ratio).astype(np.int32)
        m[: 2 * depth] = 1  # degenerate-draw floor: >= depth survivors
        masks[ratio] = m

    cfg = FakeWordsConfig(quantization=50)
    for pp in ("fp32", "int8", "int4"):
        ann = AnnIndex.build(vecs, cfg, rerank_store="int8",
                             primary_postings=pp, use_kernel=uk)

        def timed(filt):
            f = lambda: ann.search(queries, k=k, depth=depth, rerank=True,
                                   filt=filt)
            jax.block_until_ready(f())  # compile
            lat = []
            for _ in range(n_calls):
                t0 = time.perf_counter()
                jax.block_until_ready(f())
                lat.append(time.perf_counter() - t0)
            lat_ms = np.asarray(lat, np.float64) * 1e3
            _, ids = f()
            return lat_ms, ids

        lat_ms, ids = timed(None)
        _, gt = bruteforce.exact_topk(vecs, queries, k, use_kernel=uk)
        base = {
            "postings": pp, "selectivity": 1.0,
            "qps": round(batch / float(np.percentile(lat_ms, 50)) * 1e3, 1),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "recall_at_10": round(float(ev.recall_at(gt, ids)), 4),
        }
        rows.append(base)
        for ratio in ratios:
            m = masks[ratio]
            lat_ms, ids = timed(jnp.asarray(m))
            assert ((np.asarray(ids) < 0)
                    | (m[np.maximum(np.asarray(ids), 0)] != 0)).all()
            p50 = float(np.percentile(lat_ms, 50))
            rows.append({
                "postings": pp, "selectivity": ratio,
                "qps": round(batch / p50 * 1e3, 1),
                "p50_ms": round(p50, 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "recall_at_10": round(
                    float(ev.recall_at(truth_under(m), ids)), 4),
                "p50_vs_unfiltered": round(p50 / base["p50_ms"], 2),
            })
        summary[pp] = {
            "unfiltered_p50_ms": base["p50_ms"],
            "max_filtered_overhead": max(
                r["p50_vs_unfiltered"] for r in rows
                if r["postings"] == pp and r["selectivity"] < 1.0),
        }
    return rows, summary


def hybrid_ab(
    n_docs: int = 20_000, dim: int = 100, n_queries: int = 128,
    k: int = 10, k_sub: int = 30, depth: int = 100, n_calls: int = 10,
) -> Tuple[List[Dict], Dict]:
    """Hybrid lexical+dense fusion vs each retriever alone: RRF over the
    classic fake-words retriever (lexical surrogate) and the dot-scoring
    retriever (dense inner-product), k_sub-deep sub-lists fused to k
    (docs/DESIGN.md §13).  The acceptance gate — RRF recall@10 >= the best
    single retriever — needs k_sub well past k: RRF promotes docs that rank
    moderately in BOTH lists, which a k-deep sub-list truncates away.

    Runs on the word2vec-like synthetic corpus (queries are corpus words,
    the paper's setup) so the two retrievers make DIFFERENT mistakes —
    fusion has signal to exploit; on pure-noise corpora the lists correlate
    and RRF can only tie."""
    from repro.core import eval as ev
    from repro.core import plan as qp
    from repro.data import embeddings

    corpus = embeddings.make_corpus(
        embeddings.CorpusConfig(n_vectors=n_docs, dim=dim))
    queries, _ = embeddings.make_queries(corpus, n_queries)
    vecs = jnp.asarray(corpus)
    qs = jnp.asarray(queries)
    uk = None if jax.default_backend() == "tpu" else False
    _, gt = bruteforce.exact_topk(vecs, qs, k, use_kernel=uk)

    lex = AnnIndex.build(vecs, FakeWordsConfig(quantization=30), use_kernel=uk)
    dense = AnnIndex.build(
        vecs, FakeWordsConfig(quantization=30, scoring="dot"), use_kernel=uk)
    plans = (
        qp.QueryPlan(search=lambda q: lex.search(q, k=k_sub, depth=depth),
                     label="classic"),
        qp.QueryPlan(search=lambda q: dense.search(q, k=k_sub, depth=depth),
                     label="dense-dot"),
    )
    stage = qp.FusionStage(plans=plans, k=k)

    def timed(f):
        jax.block_until_ready(f())
        lat = []
        for _ in range(n_calls):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            lat.append(time.perf_counter() - t0)
        lat_ms = np.asarray(lat, np.float64) * 1e3
        _, ids = f()
        return lat_ms, ids

    rows: List[Dict] = []
    for label, f in (
        ("classic", lambda: lex.search(qs, k=k, depth=depth)),
        ("dense-dot", lambda: dense.search(qs, k=k, depth=depth)),
        ("rrf-fusion", lambda: stage.run(qs)),
    ):
        lat_ms, ids = timed(f)
        p50 = float(np.percentile(lat_ms, 50))
        rows.append({
            "retriever": label,
            "qps": round(n_queries / p50 * 1e3, 1),
            "p50_ms": round(p50, 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "recall_at_10": round(float(ev.recall_at(gt, ids[:, :k])), 4),
        })
    by = {r["retriever"]: r["recall_at_10"] for r in rows}
    summary = {
        "k": k, "k_sub": k_sub, "depth": depth,
        "classic": by["classic"], "dense": by["dense-dot"],
        "rrf": by["rrf-fusion"],
        "gate_rrf_ge_max": by["rrf-fusion"] >= max(by["classic"],
                                                   by["dense-dot"]),
    }
    return rows, summary


def packed_ab(
    n_docs: int = 8192, dim: int = 64, batch: int = 64, depth: int = 100,
    k: int = 10, n_calls: int = 20,
) -> Tuple[List[Dict], Dict]:
    """Packed single-launch vs per-segment loop (docs/DESIGN.md §14): QPS
    and p50/p99 at 1 / 4 / 16 segments over the same corpus, with the ids
    asserted identical pair-wise — the packed superbuffer is an execution
    strategy, not an approximation.  The per-segment loop pays one launch
    (encode + match + top-k + rerank + merge) per segment; packed pays one
    launch total, so the A/B spread at 16 segments IS the launch tax the
    superbuffer erases."""
    from repro.core.segments import IndexWriter

    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n_docs, dim)).astype(np.float32)
    queries = jnp.asarray(vecs[:batch])
    uk = None if jax.default_backend() == "tpu" else False
    cfg = FakeWordsConfig(quantization=50)
    rows: List[Dict] = []
    summary: Dict = {"depth": depth, "k": k, "n_docs": n_docs}

    def timed(f):
        jax.block_until_ready(f())  # compile
        lat = []
        for _ in range(n_calls):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            lat.append(time.perf_counter() - t0)
        lat_ms = np.asarray(lat, np.float64) * 1e3
        _, ids = f()
        return lat_ms, np.asarray(ids)

    for n_seg in (1, 4, 16):
        w = IndexWriter(cfg, use_kernel=uk, merge_policy=None)
        for chunk in np.array_split(vecs, n_seg):
            w.add(chunk)
            w.flush()
        reader = w.refresh()
        per_mode = {}
        for mode, flag in (("loop", False), ("packed", True)):
            lat_ms, ids = timed(
                lambda flag=flag: reader.search(
                    queries, k=k, depth=depth, rerank=True, packed=flag))
            p50 = float(np.percentile(lat_ms, 50))
            row = {
                "mode": mode, "segments": n_seg,
                "qps": round(batch / p50 * 1e3, 1),
                "p50_ms": round(p50, 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            }
            rows.append(row)
            per_mode[mode] = (row, ids)
        ids_match = bool(
            np.array_equal(per_mode["loop"][1], per_mode["packed"][1]))
        for row, _ in per_mode.values():
            row["ids_match"] = ids_match
        summary[n_seg] = {
            "loop_qps": per_mode["loop"][0]["qps"],
            "packed_qps": per_mode["packed"][0]["qps"],
            "speedup": round(per_mode["packed"][0]["qps"]
                             / per_mode["loop"][0]["qps"], 3),
            "ids_match": ids_match,
        }
    summary["gate_16seg_speedup"] = summary[16]["speedup"]
    return rows, summary


def async_ab(
    n_docs: int = 8192, dim: int = 64, n_queries: int = 256, depth: int = 100,
    k: int = 10, max_wait_ms: float = 2.0, max_batch: int = 16,
) -> Tuple[List[Dict], Dict]:
    """Async micro-batching vs sequential single-query serving at a fixed
    latency SLO (docs/DESIGN.md §14): the same ``n_queries`` singles are
    served once as back-to-back ``search_batch`` calls (one launch each)
    and once through the admission queue, where backlogged singles coalesce
    into up-to-``max_batch``-row launches.  Both run the packed segmented
    path over the same 4-segment index, so results are identical rows and
    the QPS delta is pure launch amortization."""
    from repro.core.segments import IndexWriter
    from repro.serve.ann_service import AnnService, AnnServiceConfig

    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(n_docs, dim)).astype(np.float32)
    pool = np.asarray(vecs[:n_queries])
    uk = None if jax.default_backend() == "tpu" else False
    cfg = FakeWordsConfig(quantization=50)
    w = IndexWriter(cfg, use_kernel=uk, merge_policy=None)
    for chunk in np.array_split(vecs, 4):
        w.add(chunk)
        w.flush()
    w.refresh()
    svc = AnnService(
        writer=w,
        service=AnnServiceConfig(k=k, depth=depth, rerank=True,
                                 max_batch=max_batch,
                                 max_wait_s=max_wait_ms / 1e3,
                                 queue_depth=2 * n_queries),
    )
    svc.search_batch(jnp.asarray(pool[:1]))  # compile
    svc.reset_latency()

    t0 = time.perf_counter()
    seq_ids = [np.asarray(svc.search_batch(jnp.asarray(q[None, :]))[1])
               for q in pool]
    seq_s = time.perf_counter() - t0
    seq_stats = svc.stats()

    svc.reset_latency()
    svc.start_async()
    try:
        t0 = time.perf_counter()
        futs = [svc.search_async(q) for q in pool]
        async_ids = [np.asarray(f.result(timeout=60)[1]) for f in futs]
        async_s = time.perf_counter() - t0
        st = svc.stats()
    finally:
        svc.stop_async()
    ids_match = bool(np.array_equal(np.concatenate(seq_ids),
                                    np.concatenate(async_ids)))

    rows = [
        {"mode": "sequential", "qps": round(n_queries / seq_s, 1),
         "p50_ms": seq_stats["lat_p50_ms"], "p99_ms": seq_stats["lat_p99_ms"],
         "launches": n_queries, "ids_match": ids_match},
        {"mode": "async-batched", "qps": round(n_queries / async_s, 1),
         "p50_ms": st["req_p50_ms"], "p99_ms": st["req_p99_ms"],
         "launches": st["async_launches"], "ids_match": ids_match},
    ]
    summary = {
        "n_queries": n_queries, "max_batch": max_batch,
        "max_wait_ms": max_wait_ms,
        "sequential_qps": rows[0]["qps"], "async_qps": rows[1]["qps"],
        "speedup": round(rows[1]["qps"] / rows[0]["qps"], 3),
        "batch_per_launch": round(n_queries / max(1, st["async_launches"]),
                                  2),
        "rejected": st["rejected"],
        "ids_match": ids_match,
    }
    return rows, summary


def graph_pareto(
    n_docs: int = 65_536, dim: int = 64, batch: int = 64, k: int = 10,
    n_calls: int = 15,
) -> Tuple[List[Dict], List[Dict], Dict]:
    """Recall@10-vs-p50 Pareto frontier (docs/DESIGN.md §15): the graph
    (hnsw) encoding against the paper's fake-words sweep and the exact
    oracle, all measured in ONE process on the same corpus and queries.

    Streaming encodings score every posting, so their scored-candidate
    count IS the corpus size; graph traversal scores
    ``entries + iters * beam * total_degree`` gathered rows regardless of
    N — the ``sublinear`` section records the measured counts at two
    corpus tiers (4x apart) to pin that down.  Segmented rows (1/4/16 via
    ``IndexWriter``) show the NRT fan-out price at the winning operating
    point.  Queries are in-distribution (``embeddings.make_queries``),
    the same protocol every other bench uses."""
    import dataclasses as _dc

    from repro.core import eval as ev, graph
    from repro.core.segments import IndexWriter
    from repro.core.types import GraphConfig
    from repro.data import embeddings

    uk = None if jax.default_backend() == "tpu" else False
    corpus_np = embeddings.make_corpus(
        _dc.replace(embeddings.WORD2VEC_LIKE, n_vectors=n_docs, dim=dim))
    vecs = jnp.asarray(corpus_np)
    q_np, _ = embeddings.make_queries(corpus_np, batch)
    queries = jnp.asarray(q_np)
    _, gt = bruteforce.exact_topk(vecs, queries, k, use_kernel=uk)

    def p50_of(f):
        jax.block_until_ready(f())  # compile
        lat = []
        for _ in range(n_calls):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            lat.append(time.perf_counter() - t0)
        return float(np.percentile(np.asarray(lat, np.float64) * 1e3, 50))

    rows: List[Dict] = []

    def add_row(method, params, segments, scored, ids, p50):
        rec = float(ev.recall_at(gt, jnp.asarray(ids)[:, :k]))
        rows.append({
            "method": method, "params": params, "segments": segments,
            "n_docs": n_docs, "recall_at_10": round(rec, 4),
            "p50_ms": round(p50, 2), "scored_candidates": scored,
        })
        return rows[-1]

    bf = AnnIndex.build(vecs, BruteForceConfig(), use_kernel=uk)
    f = lambda: bf.search(queries, k=k, depth=k)  # noqa: E731
    add_row("bruteforce", "exact", 1, n_docs, f()[1], p50_of(f))

    for qz, depth in ((30, 100), (50, 100), (50, 400)):
        idx = AnnIndex.build(
            vecs, FakeWordsConfig(quantization=qz), use_kernel=uk)
        f = lambda idx=idx, depth=depth: idx.search(  # noqa: E731
            queries, k=k, depth=depth, rerank=True)
        add_row("fakewords", f"q={qz},depth={depth}", 1, n_docs,
                f()[1], p50_of(f))
    fw_rows = [r for r in rows if r["method"] == "fakewords"]
    best_fw = max(fw_rows, key=lambda r: (r["recall_at_10"], -r["p50_ms"]))

    # One strong offline build, then the search-time sweep rides it — the
    # adjacency is the index, ef/beam/iters are query-time knobs.
    vn = bruteforce.l2_normalize(vecs)
    qn = bruteforce.l2_normalize(queries)
    bcfg = GraphConfig(degree=32, reverse_degree=32, ef_construction=128,
                       entries=16)
    t0 = time.perf_counter()
    nb, entry = graph.build_graph(vn, bcfg)
    jax.block_until_ready(nb)
    build_s = time.perf_counter() - t0

    def g_search(ef, beam, iters, with_stats=False):
        return graph.search_graph(
            vn, nb, entry, qn, k, ef=ef, beam=beam, iters=iters,
            n_docs=n_docs, use_kernel=uk, with_stats=with_stats)

    hnsw_rows = []
    sweep = ((16, 2, 6), (32, 4, 8), (64, 4, 8), (64, 4, 10),
             (64, 2, 16), (64, 8, 8))
    for ef, beam, iters in sweep:
        f = jax.jit(lambda ef=ef, beam=beam, iters=iters:  # noqa: E731
                    g_search(ef, beam, iters))
        _, _, sc = g_search(ef, beam, iters, with_stats=True)
        row = add_row("hnsw", f"ef={ef},beam={beam},iters={iters}", 1,
                      int(np.asarray(sc).max()), f()[1], p50_of(f))
        hnsw_rows.append((row, (ef, beam, iters)))

    dominating = [(r, p) for r, p in hnsw_rows
                  if r["recall_at_10"] >= best_fw["recall_at_10"]]
    pool = dominating or hnsw_rows
    winner, w_params = min(pool, key=lambda rp: rp[0]["p50_ms"])
    gate_pareto = bool(
        winner["recall_at_10"] >= best_fw["recall_at_10"]
        and winner["p50_ms"] < best_fw["p50_ms"])

    # NRT fan-out: same corpus split into 1 / 4 / 16 flushed segments,
    # searched through the per-segment loop (graphs have no packed layout
    # — PackedUnsupported fallback).  Smaller per-segment graphs need a
    # higher ef to hold recall — contiguous NRT slices of a clustered
    # corpus leave most queries out-of-distribution for 3 of 4 segments,
    # exactly Lucene's per-segment-HNSW cost — so the tiers run one
    # dedicated higher-effort operating point, measured at every tier.
    s_ef, s_beam, s_iters = 128, 8, 12
    seg_params = f"ef={s_ef},beam={s_beam},iters={s_iters}"
    seg_cfg = _dc.replace(bcfg, ef=s_ef, beam=s_beam, iters=s_iters)
    segments_p50 = {}
    segments_recall = {}
    f = jax.jit(lambda: g_search(s_ef, s_beam, s_iters))
    _, _, sc = g_search(s_ef, s_beam, s_iters, with_stats=True)
    row = add_row("hnsw", seg_params, 1, int(np.asarray(sc).max()),
                  f()[1], p50_of(f))
    segments_p50["1"] = row["p50_ms"]
    segments_recall["1"] = row["recall_at_10"]
    for n_seg in (4, 16):
        w = IndexWriter(seg_cfg, use_kernel=uk, merge_policy=None)
        for chunk in np.array_split(np.asarray(corpus_np), n_seg):
            w.add(chunk)
            w.flush()
        reader = w.refresh()
        f = lambda reader=reader: reader.search(queries, k=k, depth=k)  # noqa: E731,E501
        row = add_row("hnsw", seg_params, n_seg, None, f()[1], p50_of(f))
        segments_p50[str(n_seg)] = row["p50_ms"]
        segments_recall[str(n_seg)] = row["recall_at_10"]

    # Sublinearity: the same build+search params on a 4x-smaller tier of
    # the same corpus — scored candidates should barely move while the
    # streamed count drops 4x by construction.
    n_small = n_docs // 4
    w_ef, w_beam, w_iters = w_params
    vn_small = bruteforce.l2_normalize(vecs[:n_small])
    nb_s, entry_s = graph.build_graph(vn_small, bcfg)
    _, _, sc_small = graph.search_graph(
        vn_small, nb_s, entry_s, qn, k, ef=w_ef, beam=w_beam,
        iters=w_iters, n_docs=n_small, use_kernel=uk, with_stats=True)
    scored_small = int(np.asarray(sc_small).max())
    scored_full = winner["scored_candidates"]
    sub_rows = [
        {"n_docs": n_small, "scored_candidates": scored_small,
         "frac_of_corpus": round(scored_small / n_small, 4)},
        {"n_docs": n_docs, "scored_candidates": scored_full,
         "frac_of_corpus": round(scored_full / n_docs, 4)},
    ]
    gate_sublinear = bool(scored_full <= 2 * scored_small
                          and scored_full <= 0.05 * n_docs)

    summary = {
        "n_docs": n_docs, "dim": dim, "batch": batch, "k": k,
        "build_s": round(build_s, 1),
        "build_params": ("degree=32,reverse_degree=32,"
                         "ef_construction=128,entries=16"),
        "best_fakewords": {"params": best_fw["params"],
                           "recall_at_10": best_fw["recall_at_10"],
                           "p50_ms": best_fw["p50_ms"]},
        "best_hnsw": {"params": winner["params"],
                      "recall_at_10": winner["recall_at_10"],
                      "p50_ms": winner["p50_ms"],
                      "scored_candidates": winner["scored_candidates"]},
        "segments_params": seg_params,
        "segments_p50_ms": segments_p50,
        "segments_recall": segments_recall,
        "gate_pareto": gate_pareto,
        "gate_sublinear": gate_sublinear,
    }
    return rows, sub_rows, summary


def emit_bench9(
    path: str, n_docs: int = 65_536, dim: int = 64, batch: int = 64,
) -> Dict:
    """Write the graph Pareto-frontier artifact validated in CI
    (benchmarks/validate_bench9.py): recall@10 vs p50 for hnsw / fake
    words / brute force on one corpus, segmented hnsw at 1/4/16, graph
    build wall time, and scored-candidate counts at two corpus tiers."""
    rows, sub_rows, summary = graph_pareto(n_docs, dim, batch)
    bench = {
        "bench": 9,
        "backend": jax.default_backend(),
        "n_docs": n_docs,
        "dim": dim,
        "batch": batch,
        "pareto": rows,
        "sublinear": sub_rows,
        "summary": summary,
    }
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    return bench


def emit_bench8(
    path: str, n_docs: int = 8192, dim: int = 64, batch: int = 64,
) -> Dict:
    """Write the packed single-launch + async micro-batching artifact
    validated in CI (benchmarks/validate_bench8.py): packed-vs-looped
    QPS/p50/p99 at 1/4/16 segments with identical ids, and async-batched
    vs sequential single-query QPS at a fixed 2 ms coalescing SLO."""
    p_rows, p_summary = packed_ab(n_docs, dim, batch)
    a_rows, a_summary = async_ab(n_docs, dim)
    bench = {
        "bench": 8,
        "backend": jax.default_backend(),
        "n_docs": n_docs,
        "dim": dim,
        "batch": batch,
        "packed_ab": p_rows,
        "async_ab": a_rows,
        "summary": {"packed": p_summary, "async": a_summary},
    }
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    return bench


def emit_bench7(
    path: str, n_docs: int = 20_000, dim: int = 300, batch: int = 64,
) -> Dict:
    """Write the filtered + hybrid A/B artifact validated in CI
    (benchmarks/validate_bench7.py): filtered-vs-unfiltered serving at
    1%/10%/50% selectivity and RRF(classic, dense) vs each alone."""
    f_rows, f_summary = filtered_ab(n_docs, dim, batch)
    h_rows, h_summary = hybrid_ab()
    bench = {
        "bench": 7,
        "backend": jax.default_backend(),
        "n_docs": n_docs,
        "dim": dim,
        "batch": batch,
        "filtered_ab": f_rows,
        "hybrid_ab": h_rows,
        "summary": {"filtered": f_summary, "hybrid": h_summary},
    }
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    return bench


def emit_bench6(
    path: str, n_docs: int = 20_000, dim: int = 300, batch: int = 64,
) -> Dict:
    """Write the quantized-read-path A/B artifact consumed by
    :func:`repro.core.memory_budget.load_frontier` and validated in CI."""
    rows, summary = quantized_ab(n_docs, dim, batch)
    bench = {
        "bench": 6,
        "backend": jax.default_backend(),
        "n_docs": n_docs,
        "dim": dim,
        "batch": batch,
        "quantized_ab": rows,
        "summary": summary,
    }
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    return bench


def run(n_docs: int = 50_000, dim: int = 300, batch: int = 64) -> List[Dict]:
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(n_docs, dim)).astype(np.float32))
    rows = []

    cfg = FakeWordsConfig(quantization=50)
    idx = fakewords.build(vecs, cfg)
    q_tf = fakewords.encode_queries(vecs[:batch], cfg)
    f = jax.jit(lambda i, q: fakewords.classic_scores(i, q))
    dt = _time(f, idx, q_tf)
    gemm_bytes = idx.scored.size * 2 + q_tf.size * 4
    rows.append({
        "kernel": "fakewords_score(classic)", "us_per_call": dt * 1e6,
        "gflops": 2 * batch * n_docs * 2 * dim / dt / 1e9,
        "stream_mb": gemm_bytes / 1e6,
    })

    cfg_d = FakeWordsConfig(quantization=50, scoring="dot")
    idx_d = fakewords.build(vecs, cfg_d)
    f = jax.jit(lambda i, q: fakewords.dot_scores(i, q))
    dt = _time(f, idx_d, q_tf)
    rows.append({
        "kernel": "fakewords_score(dot-int8)", "us_per_call": dt * 1e6,
        "gflops": 2 * batch * n_docs * 2 * dim / dt / 1e9,
        "stream_mb": idx_d.tf.size / 1e6,
    })

    lcfg = LexicalLshConfig(buckets=300, hashes=1)
    sig = lexical_lsh.encode(vecs, lcfg)
    sq = sig[:batch]
    f = jax.jit(lexical_lsh.match_scores)
    dt = _time(f, sq, sig)
    rows.append({
        "kernel": "lsh_match", "us_per_call": dt * 1e6,
        "stream_mb": sig.size * 4 / 1e6,
    })

    from repro.core import bruteforce
    f = jax.jit(lambda c, q: bruteforce.exact_topk(c, q, 10, use_kernel=False))
    dt = _time(f, vecs, vecs[:batch])
    rows.append({
        "kernel": "bruteforce_topk", "us_per_call": dt * 1e6,
        "gflops": 2 * batch * n_docs * dim / dt / 1e9,
    })
    return rows


def _print_rows(rows: List[Dict]) -> None:
    for r in rows:
        print(",".join(f"{k}={v:.1f}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in r.items()))


def main(n_docs: int = 50_000, dim: int = 300, batch: int = 64):
    rows = run(n_docs, dim, batch)
    _print_rows(rows)
    pl_rows = pipeline_latency(n_docs, dim, batch)
    _print_rows(pl_rows)
    f_rows, summary = fused_vs_unfused(n_docs, dim, batch)
    _print_rows(f_rows)
    for scoring in ("classic", "dot"):
        s = summary[scoring]
        print(
            f"fused[{scoring}]: streams {s['fused_mb']:.1f} MB vs "
            f"{s['unfused_mb']:.1f} MB unfused "
            f"({s['stream_cut']:.1f}x less HBM traffic, no (B,N) score "
            f"matrix; wall-clock {s['speedup']:.2f}x"
            f"{' on-TPU' if summary['on_tpu'] else ' via XLA streaming ref'}; "
            f"ids_match={s['ids_match']})"
        )
    p_rows, p_summary = pruned_vs_full(n_docs, dim)
    _print_rows(p_rows)
    for mode in ("classic", "dot", "lsh"):
        s = p_summary[mode]
        print(
            f"blockmax[{mode}]: beta={p_summary['beta']} streams "
            f"{s['pruned_mb']:.1f} MB vs {s['full_mb']:.1f} MB full "
            f"({s['byte_cut']:.1f}x byte cut; wall-clock {s['speedup']:.2f}x"
            f"{' on-TPU' if p_summary['on_tpu'] else ' via XLA ref'})"
        )
    b_rows = build_bench(min(n_docs, 20_000), dim)
    _print_rows(b_rows)
    r_rows, r_summary = rerank_bench(n_docs, dim, batch)
    _print_rows(r_rows)
    print(
        f"rerank[int8]: gathers {r_summary['int8']['gather_mb']:.2f} MB vs "
        f"{r_summary['exact']['gather_mb']:.2f} MB fp32 "
        f"({r_summary['byte_cut']:.1f}x fewer rerank gather bytes; "
        f"recall@10 delta {r_summary['recall_delta']:+.4f})"
    )
    s_rows, s_summary = segments_bench(min(n_docs, 20_000), dim, min(batch, 16))
    _print_rows(s_rows)
    print(
        f"segments: 16-seg search {s_summary['fanout_cost']:.2f}x the "
        f"1-seg latency (fan-out price a background merge buys back); "
        f"merge 16->1 in {s_summary['merge_s']:.2f}s; post-merge recall@10 "
        f"{s_summary['post_merge_recall']:.3f} "
        f"(1-seg {s_summary[1]['recall']:.3f})"
    )
    q_rows, q_summary = quantized_ab(min(n_docs, 20_000), dim, batch)
    _print_rows(q_rows)
    for method, per_pp in q_summary.items():
        if not isinstance(per_pp, dict) or "int8" not in per_pp:
            continue
        print(
            f"quantized[{method}]: int8 {per_pp['int8']['bytes_cut']:.1f}x "
            f"fewer match bytes (recall@10 delta "
            f"{per_pp['int8']['recall_delta']:+.4f}), int4 "
            f"{per_pp['int4']['bytes_cut']:.1f}x (delta "
            f"{per_pp['int4']['recall_delta']:+.4f}) vs fp32"
        )
    return (
        rows + pl_rows + f_rows + p_rows + b_rows + r_rows + s_rows + q_rows,
        {**summary, "blockmax": p_summary, "rerank": r_summary,
         "segments": s_summary, "quantized": q_summary},
    )


if __name__ == "__main__":
    import sys

    if "--bench6" in sys.argv:
        out = os.path.join(os.path.dirname(__file__), "BENCH_6.json")
        bench = emit_bench6(out)
        _print_rows(bench["quantized_ab"])
        print(f"wrote {out}")
    elif "--bench7" in sys.argv:
        out = os.path.join(os.path.dirname(__file__), "BENCH_7.json")
        bench = emit_bench7(out)
        _print_rows(bench["filtered_ab"])
        _print_rows(bench["hybrid_ab"])
        h = bench["summary"]["hybrid"]
        print(f"hybrid: rrf {h['rrf']} vs classic {h['classic']} / "
              f"dense {h['dense']} (gate {h['gate_rrf_ge_max']})")
        print(f"wrote {out}")
    elif "--bench8" in sys.argv:
        out = os.path.join(os.path.dirname(__file__), "BENCH_8.json")
        bench = emit_bench8(out)
        _print_rows(bench["packed_ab"])
        _print_rows(bench["async_ab"])
        p = bench["summary"]["packed"]
        a = bench["summary"]["async"]
        print(f"packed: {p[16]['speedup']:.2f}x QPS over the per-segment "
              f"loop at 16 segments (ids_match={p[16]['ids_match']}); "
              f"async: {a['speedup']:.2f}x sequential at "
              f"{a['batch_per_launch']:.1f} rows/launch "
              f"(SLO {a['max_wait_ms']}ms)")
        print(f"wrote {out}")
    elif "--bench9" in sys.argv:
        out = os.path.join(os.path.dirname(__file__), "BENCH_9.json")
        bench = emit_bench9(out)
        _print_rows(bench["pareto"])
        _print_rows(bench["sublinear"])
        s = bench["summary"]
        print(f"pareto: hnsw {s['best_hnsw']['params']} recall "
              f"{s['best_hnsw']['recall_at_10']} @ "
              f"{s['best_hnsw']['p50_ms']}ms vs fakewords "
              f"{s['best_fakewords']['params']} "
              f"{s['best_fakewords']['recall_at_10']} @ "
              f"{s['best_fakewords']['p50_ms']}ms "
              f"(gate {s['gate_pareto']}); build {s['build_s']}s; "
              f"sublinear gate {s['gate_sublinear']}")
        print(f"wrote {out}")
    else:
        main()
