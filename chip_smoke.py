#!/usr/bin/env python3
"""Smoke run of the ANN serving path on a TPU.

    python3 chip_smoke.py                # one chip: ann-glove, served end to end
    python3 chip_smoke.py --four-chips   # doc-sharded ann-glove on a 4-chip mesh

One chip: ann-glove's corpus (``configs/ann_glove.py``: 1,193,472 x 300,
GloVe-like statistics generated from the ``GLOVE_LIKE`` seed) is ingested
through ``IndexWriter`` with the config's ``FakeWordsConfig`` (q = 50,
classic scoring, 600 fake-word columns) and served through ``AnnService``
(k = 10, depth 100, batch 256, exact rerank) with the fused Pallas match
stage compiled by Mosaic.  The run then checks:

  * the same queries on the XLA match path (``use_kernel=False``): ids agree
    on >= 99% of (query, rank) slots, and recall@10 against exact brute
    force is within 0.01 of the XLA path's;
  * 256 single queries through ``start_async``/``search_async`` return the
    ids ``search_batch`` returned;
  * one near-real-time cycle (add 32 rows, delete 4, refresh): deleted ids
    are never returned, every added row is found, and the search compiles
    nothing new;
  * the search executable holds a Mosaic kernel (``tpu_custom_call``).

``--four-chips`` ingests the same corpus through ``IndexWriter(shards=4)``
(doc-sharded over four chips), serves it through ``AnnService``, and
requires the ids of a one-chip writer of the same rows on every slot.

Timings printed on the way are those of one smoke run, not benchmark
numbers.  Every failed check exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script needs a TPU: without one it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.configs import ann_glove  # noqa: E402
from repro.core import bruteforce  # noqa: E402
from repro.core import eval as ev  # noqa: E402
from repro.core import packed as packed_mod  # noqa: E402
from repro.core.segments import IndexWriter  # noqa: E402
from repro.data import embeddings  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.serve.ann_service import AnnService, AnnServiceConfig  # noqa: E402

CELL = ann_glove.CELLS[0]
N_DOCS = CELL.extra["n_docs"]
BATCH = CELL.batch
K = CELL.extra["k"]
DEPTH = CELL.extra["depth"]
N_QUERIES = 2 * BATCH
STEADY_CALLS = 8
MIN_ID_AGREEMENT = 0.99
MAX_RECALL_GAP = 0.01


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def hbm() -> str:
    """Device 0's bytes in use and peak so far (empty off a TPU)."""
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return ""
    return (f" [HBM in use {stats['bytes_in_use']}, peak "
            f"{stats['peak_bytes_in_use']} of {stats.get('bytes_limit')}]")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {msg}")


def corpus_and_queries(n_docs: int, n_queries: int):
    cfg = dataclasses.replace(embeddings.GLOVE_LIKE, n_vectors=n_docs)
    corpus = embeddings.make_corpus(cfg)
    queries, _ = embeddings.make_queries(corpus, n_queries)
    return corpus, queries


def exact_truth(corpus: np.ndarray, queries: np.ndarray, k: int, batch: int):
    """Exact cosine top-k on the XLA path at full f32 matmul precision:
    the reference the kernel is checked against must not use the kernel."""
    c = bruteforce.l2_normalize(jnp.asarray(corpus))
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(queries), batch):
            _, ids = bruteforce.exact_topk(
                c, jnp.asarray(queries[i : i + batch]), k, use_kernel=False
            )
            out.append(np.asarray(ids))
    del c
    return np.concatenate(out)


def serve_all(svc: AnnService, queries: np.ndarray, batch: int) -> np.ndarray:
    return np.concatenate(
        [svc.search_batch(queries[i : i + batch])[1]
         for i in range(0, len(queries), batch)]
    )


def recall(truth: np.ndarray, ids: np.ndarray) -> float:
    return float(ev.recall_at(jnp.asarray(truth), jnp.asarray(ids)))


def timed_first_and_steady(svc: AnnService, queries, batch: int):
    """(first batch seconds, compile included; steady p50 ms per batch)."""
    t0 = time.perf_counter()
    svc.search_batch(queries[:batch])
    first_s = time.perf_counter() - t0
    svc.reset_latency()
    for _ in range(STEADY_CALLS):
        svc.search_batch(queries[:batch])
    return first_s, svc.stats()["lat_p50_ms"]


def one_chip(
    n_docs: int = N_DOCS, n_queries: int = N_QUERIES, batch: int = BATCH,
    kernel: bool | None = None,
) -> dict:
    """IndexWriter -> SegmentedAnnIndex -> AnnService on one device.
    ``kernel`` is the kernel path's ``use_kernel`` (None: the platform
    default, which is the Mosaic kernel on a TPU)."""
    t0 = time.perf_counter()
    corpus, queries = corpus_and_queries(n_docs, n_queries)
    truth = exact_truth(corpus, queries, K, batch)
    log(f"corpus {corpus.shape} + exact truth in {time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    writer = IndexWriter(ann_glove.make_model(CELL), rerank_store="exact")
    writer.add(corpus)
    svc = AnnService(writer=writer, service=AnnServiceConfig(
        k=K, depth=DEPTH, rerank=True, max_batch=batch, use_kernel=kernel))
    jax.block_until_ready([s.ann.index for s in svc.ann.segments])
    build_s = time.perf_counter() - t0
    log(f"ingested {n_docs} rows into {svc.ann.num_segments} segment(s) "
        f"in {build_s:.2f}s{hbm()}")

    first_s, p50 = timed_first_and_steady(svc, queries, batch)
    log(f"kernel path: first batch {first_s:.2f}s (compile included), "
        f"steady p50 {p50} ms/batch (smoke timing){hbm()}")
    ids_k = serve_all(svc, queries, batch)

    svc_x = AnnService(svc.ann, AnnServiceConfig(
        k=K, depth=DEPTH, rerank=True, max_batch=batch, use_kernel=False))
    first_x, p50_x = timed_first_and_steady(svc_x, queries, batch)
    log(f"XLA path: first batch {first_x:.2f}s (compile included), "
        f"steady p50 {p50_x} ms/batch (smoke timing){hbm()}")
    ids_x = serve_all(svc_x, queries, batch)

    agree = float(np.mean(ids_k == ids_x))
    rec_k, rec_x = recall(truth, ids_k), recall(truth, ids_x)
    log(f"id agreement kernel vs XLA {agree:.4f}; recall@{K} kernel "
        f"{rec_k:.4f}, XLA {rec_x:.4f}")
    check(agree >= MIN_ID_AGREEMENT, f"id agreement {agree} < {MIN_ID_AGREEMENT}")
    check(abs(rec_k - rec_x) <= MAX_RECALL_GAP,
          f"recall gap {rec_k - rec_x} exceeds {MAX_RECALL_GAP}")

    svc.start_async()
    try:
        futs = [svc.search_async(q) for q in queries[:batch]]
        ids_a = np.concatenate([f.result(timeout=600)[1] for f in futs])
        launches = svc.stats()["async_launches"]
    finally:
        svc.stop_async()
    log(f"async: {len(futs)} single queries in {launches} launches")
    check(np.array_equal(ids_a, ids_k[:batch]), "async ids differ from batch ids")

    # The XLA service holds the pre-refresh snapshot; drop it so that
    # snapshot's packed buffers are freed before the refresh repacks.
    del svc_x
    compiles = packed_mod.EXEC_CACHE.compiles
    new_rows = embeddings.make_corpus(dataclasses.replace(
        embeddings.GLOVE_LIKE, n_vectors=32, seed=embeddings.GLOVE_LIKE.seed + 1))
    new_ids = writer.add(new_rows)
    dead = np.unique(truth[:4, 0])
    writer.delete(dead)
    t0 = time.perf_counter()
    svc.refresh()
    log(f"NRT refresh (flush of the added rows) in "
        f"{time.perf_counter() - t0:.2f}s{hbm()}")
    t0 = time.perf_counter()
    packed = svc.ann.packed_segments()  # global-stat views + repack
    if packed is not None:
        jax.block_until_ready(packed.view)
    log(f"NRT stat views + repack in {time.perf_counter() - t0:.2f}s{hbm()}")
    ids_after = serve_all(svc, queries, batch)
    _, ids_new = svc.search_batch(new_rows)
    stats = svc.stats()
    path = ("packed single launch" if stats["packed_bucket"] is not None
            else f"per-segment loop ({stats.get('packed_unsupported')})")
    nrt_compiles = packed_mod.EXEC_CACHE.compiles - compiles
    log(f"NRT cycle: +{len(new_ids)} rows, -{len(dead)} rows, "
        f"{stats['segments']} segments, served by the {path}; "
        f"search compiles in the cycle: {nrt_compiles}{hbm()}")
    cache = packed_mod.EXEC_CACHE.stats()
    log(f"ExecutableCache: {cache}")
    check(not np.isin(ids_after, dead).any(), "a deleted id was returned")
    check(all(i in row for i, row in zip(new_ids, ids_new)),
          "an added row was not found by its own query")
    check(nrt_compiles == 0, f"the NRT cycle compiled {nrt_compiles} executables")
    return {
        "build_s": build_s, "first_batch_s": first_s, "p50_ms": p50,
        "xla_first_batch_s": first_x, "xla_p50_ms": p50_x,
        "id_agreement": agree, "recall_kernel": rec_k, "recall_xla": rec_x,
        "serving_path": path, "exec_cache": cache,
    }


def four_chips(
    n_docs: int = N_DOCS, n_queries: int = N_QUERIES, batch: int = BATCH,
    n_chips: int = 4, kernel: bool | None = None,
) -> dict:
    """The corpus doc-sharded by ``IndexWriter(shards=n_chips)`` and served
    by ``AnnService``, against a one-chip writer of the same rows served the
    same way.  The sharded merge takes the global top-depth by match score
    before the rerank, so the ids must agree on every slot, with and
    without the rerank.  The sharded writer is freed before the one-chip
    one is built, so chip 0 never holds both."""
    t0 = time.perf_counter()
    corpus, queries = corpus_and_queries(n_docs, n_queries)
    truth = exact_truth(corpus, queries, K, batch)
    log(f"corpus {corpus.shape} + exact truth in {time.perf_counter() - t0:.2f}s")
    cfg = ann_glove.make_model(CELL)
    ids = {}
    for shards in (n_chips, 1):
        t0 = time.perf_counter()
        writer = IndexWriter(cfg, rerank_store="exact", shards=shards)
        writer.add(corpus)
        writer.flush()
        log(f"IndexWriter(shards={shards}) ingest in {time.perf_counter() - t0:.2f}s{hbm()}")
        for rerank in (False, True):
            scfg = AnnServiceConfig(k=K, depth=DEPTH, rerank=rerank,
                                    max_batch=batch, use_kernel=kernel)
            svc = AnnService(writer=writer, service=scfg)
            first_s, p50 = timed_first_and_steady(svc, queries, batch)
            ids[shards, rerank] = serve_all(svc, queries, batch)
            log(f"shards={shards} rerank={rerank}: first batch {first_s:.2f}s "
                f"(compile included), steady p50 {p50} ms/batch (smoke timing)")
            del svc
        del writer
    out = {}
    for rerank in (False, True):
        agree = float(np.mean(ids[n_chips, rerank] == ids[1, rerank]))
        rec_s, rec_1 = recall(truth, ids[n_chips, rerank]), recall(truth, ids[1, rerank])
        tag = "rerank" if rerank else "match only"
        log(f"{tag}: id agreement {n_chips} chips vs one {agree:.4f}; recall@{K} "
            f"{n_chips} chips {rec_s:.4f}, one {rec_1:.4f}")
        out[tag] = {"id_agreement": agree, "recall_sharded": rec_s, "recall_one": rec_1}
        check(agree == 1.0, f"{tag}: sharded ids agree on {agree} of slots, not all")
    return out


def check_mosaic_kernel() -> None:
    """The served search executables must hold a Mosaic kernel."""
    texts = [exe.as_text() for exe in packed_mod.EXEC_CACHE.executables("search")]
    check(bool(texts), "no search executable was compiled")
    check(any("tpu_custom_call" in t for t in texts),
          "no search executable contains tpu_custom_call")
    log(f"tpu_custom_call found in the search executable "
        f"({sum('tpu_custom_call' in t for t in texts)}/{len(texts)})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the doc-sharded four-chip path")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[smoke] needs a TPU, JAX found {dev.platform}")
    check(not common.INTERPRET, "Pallas interpret mode is on")
    check(common.USE_KERNEL_DEFAULT, "the fused kernel is not the default path")
    n_chips = 4 if args.four_chips else 1
    check(len(devices) >= n_chips, f"needs {n_chips} chips, found {len(devices)}")
    cache_dir = compile_cache.enable()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"{dev.device_kind} x {len(devices)}; compile cache {cache_dir} "
        f"({n_cached} entries at start)")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(n_chips=n_chips)
    else:
        one_chip()
        check_mosaic_kernel()
    stats = dev.memory_stats() or {}
    n_after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on device 0; "
        f"compile cache entries {n_cached} -> {n_after}; "
        f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
