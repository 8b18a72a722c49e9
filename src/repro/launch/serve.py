"""End-to-end ANN serving driver (the paper's system, running for real).

    PYTHONPATH=src python -m repro.launch.serve --n-docs 100000 --queries 512
    PYTHONPATH=src python -m repro.launch.serve --method lsh
    PYTHONPATH=src python -m repro.launch.serve --method hnsw --ef 128
    PYTHONPATH=src python -m repro.launch.serve --save-index /tmp/idx.ann
    PYTHONPATH=src python -m repro.launch.serve --quantized-rerank
    PYTHONPATH=src python -m repro.launch.serve --segments 8
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve --shards 8

Builds an AnnIndex (any encoding: fake words / lexical LSH / kd-scan /
brute force) over a synthetic word2vec-like corpus, stands up the batched
AnnService over it, replays a query stream, and reports R@(k,d) against the
brute-force oracle plus the service's own latency percentiles.  With
``--save-index`` the index round-trips through ``AnnIndex.save`` /
``AnnIndex.load`` first — the ship-to-serving-process path.  With
``--shards N`` the index builds THROUGH the distributed BuildPipeline
(docs/DESIGN.md §8: row-parallel under ``shard_map``, no full-corpus
materialization on any shard) and serves through the pod fan-out/merge
path; ``--quantized-rerank`` swaps the rerank store for the int8 + per-doc
scale QuantizedStore (~4x fewer rerank gather bytes).

With ``--segments N`` the corpus is INGESTED ONLINE through the Lucene-style
``IndexWriter`` (docs/DESIGN.md §11): the service starts on the first chunk
and the remaining chunks arrive between query rounds via
``writer.add`` + ``service.refresh()`` — near-real-time serving with the
epoch-keyed result cache; 10% of the corpus is then deleted and the index
force-merged to one segment, demonstrating the full segment lifecycle the
frozen facade cannot express.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import bruteforce, distributed, eval as ev
from repro.core.index import AnnIndex
from repro.core.segments import IndexWriter
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    GraphConfig,
    KdTreeConfig,
    LexicalLshConfig,
)
from repro.data import embeddings
from repro.serve.ann_service import AnnService, AnnServiceConfig


def make_config(args):
    if args.method == "fakewords":
        # df_max_ratio defaults OFF: the paper's high-df filtering threshold
        # is corpus-dependent, and on the dense synthetic corpora every term
        # exceeds df = 0.25*N — a hard-coded 0.25 zeroed every query term
        # (recall 0).  Sweep it via benchmarks/ablations.py instead.
        return FakeWordsConfig(quantization=args.q, df_max_ratio=args.df_max_ratio)
    if args.method == "lsh":
        return LexicalLshConfig(buckets=300, hashes=1)
    if args.method == "kdtree":
        return KdTreeConfig(dims=8, backend="scan")
    if args.method == "bruteforce":
        return BruteForceConfig()
    if args.method == "hnsw":
        return GraphConfig(ef=args.ef, beam=args.beam)
    raise ValueError(f"unknown method {args.method}")


def serve_segmented(args, corpus, queries) -> dict:
    """Online-ingestion serving loop: start on the first chunk, stream the
    rest through ``writer.add`` + ``service.refresh()`` between query
    rounds, then delete 10% and force-merge — the segment lifecycle end to
    end, with recall measured against the final live corpus."""
    rng = np.random.default_rng(0)
    config = make_config(args)
    writer = IndexWriter(
        config,
        rerank_store="int8" if args.quantized_rerank else "exact",
        primary_postings=args.postings or "fp32",
    )
    chunks = np.array_split(np.asarray(corpus), args.segments)
    t0 = time.time()
    writer.add(chunks[0])
    svc = AnnService(writer=writer, service=AnnServiceConfig(
        k=args.k, depth=args.depth, rerank=args.rerank,
        max_batch=args.batch, cache_size=64))
    svc.search_batch(queries[: args.batch])  # warmup/compile
    svc.reset_latency()
    for chunk in chunks[1:]:
        writer.add(chunk)
        svc.refresh()
        svc.search_batch(queries[: args.batch])  # serve between ingests
    ingest_s = time.time() - t0
    # Delete a random 10% of everything ingested, then serve the rest.
    dead = rng.choice(args.n_docs, size=args.n_docs // 10, replace=False)
    writer.delete(dead)
    svc.refresh()
    n_seg_before = svc.ann.num_segments
    ids_all = []
    for i in range(0, len(queries), args.batch):
        _, ids = svc.search_batch(queries[i : i + args.batch])
        ids_all.append(ids)
    ids_all = np.concatenate(ids_all)
    # Ground truth over the LIVE corpus, mapped to stable global ids.
    live = np.ones(args.n_docs, bool)
    live[dead] = False
    gmap = svc.ann.live_global_ids()
    _, gt_i = bruteforce.exact_topk(
        jnp.asarray(np.asarray(corpus)[live]), jnp.asarray(queries), args.k)
    gt_global = gmap[np.asarray(gt_i)]
    recall = float(ev.recall_at(jnp.asarray(gt_global), jnp.asarray(ids_all)))
    t1 = time.time()
    writer.force_merge(1)
    svc.refresh()
    merge_s = time.time() - t1
    stats = svc.stats()
    out = {
        "method": svc.ann.method,
        "recall@k": round(recall, 4),
        "p50_ms_per_batch": stats["lat_p50_ms"],
        "p99_ms_per_batch": stats["lat_p99_ms"],
        "segments_before_merge": n_seg_before,
        "merge_s": round(merge_s, 2),
        "ingest_s": round(ingest_s, 2),
        "live_docs": stats["num_docs"],
        "epoch": stats["epoch"],
        "cache": (stats["cache_hits"], stats["cache_misses"]),
    }
    print(f"[serve] segmented NRT {out}")
    return out


def zipf_sampler(rng, pool: int, s: float):
    """Zipfian rank-frequency sampler over a query pool — real query
    streams are heavily head-skewed, which is what makes result caches and
    micro-batch coalescing pay."""
    ranks = np.arange(1, pool + 1, dtype=np.float64)
    p = ranks ** -s
    p /= p.sum()
    return lambda n: rng.choice(pool, size=n, p=p)


def serve_openloop(args, corpus, queries) -> dict:
    """Open-loop traffic generator (docs/DESIGN.md §14): arrivals at a
    FIXED ``--qps`` schedule (independent of service speed — the honest
    way to measure tail latency), Zipfian reuse over a query pool, and
    mixed add/delete/search against the NRT writer.  Reports sustained
    QPS + per-request p50/p99 for the async micro-batcher next to a
    sequential single-query A/B over the same workload."""
    import queue as queue_mod

    rng = np.random.default_rng(13)
    config = make_config(args)
    writer = IndexWriter(
        config,
        rerank_store="int8" if args.quantized_rerank else "exact",
        primary_postings=args.postings or "fp32",
    )
    n0 = max(args.batch, int(args.n_docs * 0.9))
    corpus = np.asarray(corpus)
    writer.add(corpus[:n0])
    ingest_ptr = n0
    svc = AnnService(writer=writer, service=AnnServiceConfig(
        k=args.k, depth=args.depth, rerank=args.rerank,
        max_batch=args.batch,
        max_wait_s=args.max_wait_ms / 1e3, queue_depth=args.queue_depth))
    pool = min(args.query_pool, len(queries))
    pool_q = np.asarray(queries)[:pool]
    sample = zipf_sampler(rng, pool, args.zipf_s)
    svc.search_batch(pool_q[: args.batch])  # warmup/compile
    svc.reset_latency()

    # -- sequential A/B: the same Zipfian stream, one query per launch ----
    seq_n = max(32, min(512, int(args.qps * args.duration / 4)))
    seq_idx = sample(seq_n)
    t0 = time.perf_counter()
    for i in seq_idx:
        svc.search_batch(pool_q[int(i) : int(i) + 1])
    seq_qps = seq_n / (time.perf_counter() - t0)
    svc.reset_latency()

    # -- open loop: submit on the wall-clock schedule, never wait ---------
    svc.start_async()
    period = 1.0 / args.qps
    futs, shed, sent = [], 0, 0
    start = time.perf_counter()
    next_t = start
    t_end = start + args.duration
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        if now < next_t:
            time.sleep(min(next_t - now, 1e-3))
            continue
        next_t += period
        i = int(sample(1)[0])
        try:
            futs.append(svc.search_async(pool_q[i]))
            sent += 1
        except queue_mod.Full:
            shed += 1
        if args.mutate_every and sent and sent % args.mutate_every == 0:
            # Mixed workload: ingest a small chunk + delete a few docs,
            # then refresh — the packed executable cache keeps these
            # NRT cycles compile-free (same bucket rung).
            if ingest_ptr < len(corpus):
                writer.add(corpus[ingest_ptr : ingest_ptr + 32])
                ingest_ptr += 32
            writer.delete(rng.choice(ingest_ptr, size=4, replace=False))
            svc.refresh()
    for f in futs:
        f.result(timeout=120)
    elapsed = time.perf_counter() - start
    svc.stop_async()
    stats = svc.stats()
    out = {
        "method": svc.ann.method,
        "offered_qps": args.qps,
        "sustained_qps": round(len(futs) / elapsed, 1),
        "sequential_qps": round(seq_qps, 1),
        "req_p50_ms": stats["req_p50_ms"],
        "req_p99_ms": stats["req_p99_ms"],
        "async_launches": stats["async_launches"],
        "batch_per_launch": round(len(futs) / max(1, stats["async_launches"]), 1),
        "shed": shed,
        "live_docs": stats["num_docs"],
        "segments": stats["segments"],
    }
    print(f"[serve] open-loop {out}")
    return out


def serve_filtered(args, svc, corpus, queries, ratios, unfiltered) -> list:
    """Filtered smoke: replay the SAME query stream under random predicate
    bitmaps at each selectivity, through the match stage's single in-kernel
    filtered pass (docs/DESIGN.md §13).  Recall is measured against exact
    brute force over the FILTERED corpus; latency percentiles print next to
    the unfiltered ones from the main replay."""
    rng = np.random.default_rng(7)
    results = []
    for ratio in ratios:
        mask = rng.random(args.n_docs) < ratio
        if mask.sum() < args.k:  # degenerate draw at tiny selectivity
            mask[rng.choice(args.n_docs, size=args.k, replace=False)] = True
        filt = mask.astype(np.int32)
        svc.search_batch(queries[: args.batch], filter=filt)  # compile
        svc.reset_latency()
        ids_all = []
        for i in range(0, len(queries), args.batch):
            _, ids = svc.search_batch(queries[i : i + args.batch], filter=filt)
            ids_all.append(ids)
        ids_all = np.concatenate(ids_all)
        kept = np.flatnonzero(mask)
        _, gt_i = bruteforce.exact_topk(
            jnp.asarray(np.asarray(corpus)[kept]), jnp.asarray(queries), args.k
        )
        gt_global = kept[np.asarray(gt_i)]
        recall = float(
            ev.recall_at(jnp.asarray(gt_global), jnp.asarray(ids_all))
        )
        stats = svc.stats()
        row = {
            "selectivity": ratio,
            "recall@k": round(recall, 4),
            "p50_ms_per_batch": stats["lat_p50_ms"],
            "p99_ms_per_batch": stats["lat_p99_ms"],
        }
        results.append(row)
        print(
            f"[serve] filtered {ratio:.0%}: recall@k {row['recall@k']} "
            f"p50 {row['p50_ms_per_batch']}ms p99 {row['p99_ms_per_batch']}ms"
            f" (unfiltered: p50 {unfiltered['p50_ms_per_batch']}ms "
            f"p99 {unfiltered['p99_ms_per_batch']}ms)"
        )
    return results


def serve_hybrid(args, ann, corpus, queries) -> dict:
    """Hybrid smoke: RRF-fuse a lexical classic fake-words retriever with a
    dense kd-scan retriever over the same corpus (core/plan.py FusionStage)
    and report recall@k of the fusion next to each retriever alone."""
    from repro.core import plan as qplan

    cv = jnp.asarray(corpus)
    lex = (
        ann
        if isinstance(ann.config, FakeWordsConfig)
        and ann.config.scoring == "classic"
        else AnnIndex.build(cv, FakeWordsConfig(quantization=args.q))
    )
    dense = AnnIndex.build(cv, KdTreeConfig(dims=8, backend="scan"))
    sub = {
        "classic": qplan.QueryPlan(
            search=lambda q: lex.search(q, k=args.k, depth=args.depth),
            label="classic",
        ),
        "dense": qplan.QueryPlan(
            search=lambda q: dense.search(q, k=args.k, depth=args.depth),
            label="dense",
        ),
    }
    fusion = qplan.FusionStage(plans=tuple(sub.values()), k=args.k)
    qv = jnp.asarray(queries)
    _, gt_i = bruteforce.exact_topk(cv, qv, args.k)
    gt = jnp.asarray(np.asarray(gt_i))
    rec = {
        name: round(float(ev.recall_at(gt, p.run(qv)[1])), 4)
        for name, p in sub.items()
    }
    _, fused_i = fusion.run(qv)
    rec["hybrid_rrf"] = round(float(ev.recall_at(gt, fused_i)), 4)
    print(
        f"[serve] hybrid recall@{args.k}: classic {rec['classic']} "
        f"dense {rec['dense']} rrf {rec['hybrid_rrf']}"
    )
    return rec


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=300)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument(
        "--method",
        choices=("fakewords", "lsh", "kdtree", "bruteforce", "hnsw"),
        default="fakewords",
    )
    ap.add_argument("--q", type=int, default=50, help="fake-words quantization")
    ap.add_argument("--ef", type=int, default=64,
                    help="hnsw search list width (recall/latency knob)")
    ap.add_argument("--beam", type=int, default=4,
                    help="hnsw nodes expanded per traversal iteration")
    ap.add_argument("--df-max-ratio", type=float, default=1.0,
                    help="search-time high-df term filtering (1.0 = off)")
    ap.add_argument("--depth", type=int, default=100)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--rerank", action="store_true", default=True)
    ap.add_argument("--blockmax-keep", type=int, default=None)
    ap.add_argument(
        "--save-index", default=None,
        help="save the built index here and serve from the loaded copy",
    )
    ap.add_argument(
        "--shards", type=int, default=0,
        help="build AND serve doc-sharded over this many devices "
             "(distributed BuildPipeline; needs >= N jax devices, e.g. "
             "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--quantized-rerank", action="store_true",
        help="rerank from the int8 + per-doc-scale QuantizedStore instead "
             "of fp32 originals (~4x fewer rerank gather bytes)",
    )
    ap.add_argument(
        "--postings", choices=("fp32", "int8", "int4"), default=None,
        help="primary postings encoding: int8 (per-doc scale) or int4 "
             "(grouped scales), dequantized inside the fused score stage "
             "(docs/DESIGN.md §12); default fp32 unless --memory-budget "
             "picks otherwise",
    )
    ap.add_argument(
        "--memory-budget", type=float, default=None, metavar="MB",
        help="resident index budget in MB; picks the best-recall "
             "{postings, rerank store, blockmax keep} that fits "
             "(core/memory_budget.py); knobs set explicitly are pinned",
    )
    ap.add_argument(
        "--segments", type=int, default=0,
        help="ingest the corpus ONLINE in this many chunks through the "
             "Lucene-style IndexWriter (segmented NRT serving with "
             "deletes + a forced merge; docs/DESIGN.md §11)",
    )
    ap.add_argument(
        "--filter-ratio", type=float, nargs="*", default=None,
        metavar="RATIO",
        help="filtered-search smoke: replay the query stream under random "
             "predicate bitmaps at these selectivities (bare flag = "
             "1%%/10%%/50%%), logging filtered p50/p99 and recall next to "
             "the unfiltered numbers (docs/DESIGN.md §13)",
    )
    ap.add_argument(
        "--qps", type=float, default=0,
        help="open-loop traffic generator: submit single queries to the "
             "async micro-batcher at this fixed arrival rate (Zipfian "
             "reuse over --query-pool, mixed add/delete/search via "
             "--mutate-every) and report sustained QPS + per-request "
             "p50/p99 next to a sequential single-query A/B",
    )
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop run length in seconds")
    ap.add_argument("--query-pool", type=int, default=256,
                    help="distinct queries in the Zipfian reuse pool")
    ap.add_argument("--zipf-s", type=float, default=1.1,
                    help="Zipf skew exponent for query reuse")
    ap.add_argument(
        "--mutate-every", type=int, default=200,
        help="every N requests: add a 32-doc chunk, delete 4 docs, "
             "refresh (0 = search-only traffic)",
    )
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="async micro-batch window (the SLO's donation)")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="async admission queue bound (backpressure)")
    ap.add_argument(
        "--hybrid", action="store_true",
        help="hybrid smoke: RRF-fuse the lexical classic fake-words "
             "retriever with a dense kd-scan retriever over the same "
             "corpus (core/plan.py FusionStage) and log recall@k of the "
             "fusion next to each retriever alone",
    )
    args = ap.parse_args(argv)
    compile_cache.enable()

    corpus = embeddings.make_corpus(
        embeddings.CorpusConfig(n_vectors=args.n_docs, dim=args.dim)
    )
    queries, qids = embeddings.make_queries(corpus, args.queries)

    if args.qps:
        if args.shards or args.segments:
            raise SystemExit(
                "--qps drives the async NRT writer path; it is not "
                "combined with --shards/--segments"
            )
        return serve_openloop(args, corpus, queries)

    if args.segments:
        if args.shards:
            raise SystemExit("--segments and --shards are mutually exclusive")
        if args.filter_ratio is not None or args.hybrid:
            raise SystemExit(
                "--filter-ratio/--hybrid smoke modes run on the monolithic "
                "serving path; drop --segments (segmented filtering is "
                "exercised by tests/test_filtered.py)"
            )
        if args.save_index:
            raise SystemExit(
                "--segments persists via IndexWriter.commit, not "
                "--save-index; use writer.commit(path) / "
                "SegmentedAnnIndex.load(path)"
            )
        if args.memory_budget is not None:
            raise SystemExit(
                "--memory-budget plans a monolithic build; with --segments "
                "pass --postings/--quantized-rerank explicitly"
            )
        return serve_segmented(args, corpus, queries)

    mesh = None
    if args.shards:
        if args.method == "hnsw":
            raise SystemExit(
                "--shards serves shard-local match + merge, which graph "
                "traversal cannot do (adjacency edges cross shard "
                "boundaries); serve hnsw with --segments N or single-device "
                "(the sharded BUILD is exercised by tests/test_graph.py)"
            )
        n_dev = len(jax.devices())
        if n_dev < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs >= {args.shards} devices, "
                f"found {n_dev}; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={args.shards}"
            )
        mesh = distributed.make_mesh((args.shards,), ("data",))

    config = make_config(args)
    rerank_store = "int8" if args.quantized_rerank else (
        None if args.memory_budget is not None else "exact")
    budget = (int(args.memory_budget * 1e6)
              if args.memory_budget is not None else None)
    t0 = time.time()
    ann = AnnIndex.build(
        jnp.asarray(corpus), config,
        rerank_store=rerank_store, mesh=mesh, shard_axes=("data",),
        primary_postings=args.postings,
        memory_budget_bytes=budget,
    )
    jax.block_until_ready(jax.tree_util.tree_leaves(ann.index))
    build_s = time.time() - t0
    if mesh is not None:
        # On a real multi-host mesh shards build concurrently, so this wall
        # time IS the per-shard build time; under simulated host devices
        # the shards share one host's cores and it is the total.
        print(f"[serve] sharded build: {args.shards} shards x "
              f"{args.n_docs // args.shards} docs, build wall time "
              f"{build_s:.2f}s (= per-shard on a multi-host mesh; "
              f"no full-corpus materialization)")
    print(f"[serve] indexed {args.n_docs} docs ({ann.method}"
          f"{', int8 rerank store' if args.quantized_rerank else ''}) "
          f"in {build_s:.1f}s ({ann.nbytes()/1e6:.0f} MB)")

    if args.save_index:
        ann.save(args.save_index)
        ann = AnnIndex.load(args.save_index)
        print(f"[serve] round-tripped index through {args.save_index}")

    # A budget plan may select rerank_store="none"; serving then runs
    # match-only regardless of --rerank.
    do_rerank = args.rerank and (
        ann.index.vectors is not None
        or getattr(ann.index, "vq", None) is not None
    )
    svc = AnnService(ann, AnnServiceConfig(
        k=args.k, depth=args.depth, rerank=do_rerank, max_batch=args.batch,
        blockmax_keep=args.blockmax_keep),
        mesh=mesh, shard_axes=("data",) if mesh is not None else ())

    # Warmup (compile) then timed replay; drop the compile batch's wall time
    # so the reported percentiles reflect steady-state serving latency.
    svc.search_batch(queries[: args.batch])
    svc.reset_latency()
    ids_all = []
    for i in range(0, len(queries), args.batch):
        _, ids = svc.search_batch(queries[i : i + args.batch])
        ids_all.append(ids)
    ids_all = np.concatenate(ids_all)

    gt_s, gt_i = bruteforce.exact_topk(jnp.asarray(corpus), jnp.asarray(queries), args.k)
    recall = float(ev.recall_at(jnp.asarray(np.asarray(gt_i)), jnp.asarray(ids_all)))
    stats = svc.stats()
    out = {
        "method": ann.method,
        "recall@k": round(recall, 4),
        "p50_ms_per_batch": stats["lat_p50_ms"],
        "p99_ms_per_batch": stats["lat_p99_ms"],
        "index_mb": round(ann.nbytes() / 1e6, 1),
        "queries": int(svc.queries_served),
    }
    print(f"[serve] {out}")

    if args.filter_ratio is not None:
        ratios = args.filter_ratio if args.filter_ratio else [0.01, 0.1, 0.5]
        out["filtered"] = serve_filtered(
            args, svc, corpus, queries, ratios, out
        )
    if args.hybrid:
        out["hybrid"] = serve_hybrid(args, ann, corpus, queries)
    return out


if __name__ == "__main__":
    main()
