"""Batched ANN query service over any AnnIndex — single-device, sharded, or
segmented (near-real-time).

The serving-side realization of the paper: a query stream is micro-batched
(latency/throughput knob), encoded through the index's pipeline encoder
(tf row / MinHash signature / reduced point / identity), and searched
through the SAME staged pipeline as offline search — single-device under
``jit``, pod-sharded via ``core/distributed.py`` (local match stage +
local top-d + local rerank + tiny all-gather merge, the Lucene
query-fan-out/merge architecture), or across the segments of a mutable
:class:`repro.core.segments.SegmentedAnnIndex`, one jit'd function per
batch (per segment, when segmented).

Every encoding — fake words, lexical LSH, k-d scan, brute force — serves
through one code path; there are no per-method branches here.  An index
built offline ships in via ``AnnIndex.load`` (see ``core/index.py``) or
``SegmentedAnnIndex.load`` (a commit point).  Indexes carrying the int8
:class:`repro.core.types.QuantizedStore` rerank automatically through the
quantized gather (single-device AND sharded), and
``AnnServiceConfig.cache_size`` enables the per-shard LRU result cache
keyed on the encoded query representation (docs/DESIGN.md §8).

**Online serving** (docs/DESIGN.md §11): construct with ``writer=`` (an
:class:`repro.core.segments.IndexWriter`) and call :meth:`AnnService.refresh`
after ingesting — the service re-points at the writer's latest NRT
snapshot.  Every searchable snapshot carries a process-unique **epoch**
(:func:`repro.core.types.next_epoch`) that joins the result-cache key, so
a refresh (or an explicit :meth:`AnnService.set_index` swap) can never
serve another index generation's cached results.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import hashlib
import itertools
import queue as queue_mod
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core import bruteforce, distributed
from repro.core import packed as packed_mod
from repro.core import pipeline as pl
from repro.core.index import AnnIndex, AnyConfig, AnyIndex
from repro.core.segments import IndexWriter, SegmentedAnnIndex
from repro.core.types import FakeWordsIndex, LshIndex


@dataclasses.dataclass
class AnnServiceConfig:
    k: int = 10
    depth: int = 100
    rerank: bool = True
    max_batch: int = 64       # micro-batch size (pad to this)
    # Async micro-batcher (docs/DESIGN.md §14): a queued request launches
    # once the coalesced batch reaches ``max_batch`` rows OR the OLDEST
    # queued request has waited ``max_wait_s`` — the batching window is the
    # latency the SLO donates to throughput.  ``queue_depth`` bounds the
    # admission queue; search_async raises queue.Full past it
    # (backpressure — shed at the door, don't grow tail latency).
    max_wait_s: float = 0.002
    queue_depth: int = 256
    # Route the match phase through the fused streaming score->top-k Pallas
    # kernel (docs/DESIGN.md §4).  None = kernel on TPU, XLA elsewhere.
    use_kernel: Optional[bool] = None
    # Two-stage blockmax pruning (docs/DESIGN.md §6): keep this many blocks
    # per query (per shard when sharded) in the match phase.  None disables.
    # Cuts streamed index bytes ~(1 - kept/total) at a small recall cost.
    # Fake-words and LSH indexes only (segmented serving rides the packed
    # superbuffer, docs/DESIGN.md §14).
    blockmax_keep: Optional[int] = None
    blockmax_block_size: int = 256
    # Per-shard result cache (ROADMAP follow-up): LRU over the last
    # ``cache_size`` micro-batches, keyed on the hash of the ENCODED query
    # representation bytes + the effective SearchParams/knobs + the index
    # EPOCH (so swapping or refreshing the index invalidates) — a repeated
    # query stream skips the match+rerank entirely on this serving shard.
    # 0 disables.  Hit/miss counters surface in stats().
    cache_size: int = 0


def _pad_rows(x: np.ndarray, rows: int) -> np.ndarray:
    """``x`` with zero rows appended up to ``rows``."""
    pad = rows - x.shape[0]
    if not pad:
        return x
    return np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)], 0)


class AnnService:
    """Single-device, sharded, or segmented search service over any
    AnnIndex / SegmentedAnnIndex."""

    def __init__(
        self,
        index: Union[AnnIndex, SegmentedAnnIndex, AnyIndex, None] = None,
        config: Optional[AnyConfig] = None,
        service: Optional[AnnServiceConfig] = None,
        mesh: Optional[Mesh] = None,
        shard_axes: Sequence[str] = (),
        writer: Optional[IndexWriter] = None,
    ):
        if writer is not None:
            if index is not None:
                raise ValueError("pass index= or writer=, not both")
            index = writer.refresh()
        self.writer = writer
        if index is None:
            raise ValueError("AnnService needs an index or a writer")
        if isinstance(index, (AnnIndex, SegmentedAnnIndex)):
            # AnnService(ann) / AnnService(ann, service_cfg) forms.
            if service is None and isinstance(config, AnnServiceConfig):
                config, service = None, config
            if config is not None and config != index.config:
                raise ValueError(
                    "method config passed alongside an AnnIndex disagrees "
                    f"with the index's own config ({config} != {index.config})"
                )
            ann = index
        else:
            ann = AnnIndex(config=config, index=index)
        self.scfg = service if service is not None else AnnServiceConfig()
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes)
        # One lock covers every snapshot swap (_bind) and every search —
        # the async worker thread and caller threads share this service.
        self._lock = threading.RLock()
        self._bind(ann)
        self.queries_served = 0
        self.batches = 0
        # Per-launch wall times (stats() lat_*), and per-REQUEST
        # enqueue->result times on the async path (req_*), kept apart so
        # SLO percentiles are honest (queue wait included, batch fan-in not
        # averaged away).
        self._lat = obs.LatencyHistogram()
        self._req_lat = obs.LatencyHistogram()
        # Async requests' waits from enqueue to the start of their launch.
        self.queue_wait_s = 0.0
        self.async_requests = 0
        # Ids that tie a request's or a launch's spans together.
        self._req_ids = itertools.count()
        self._launch_ids = itertools.count()
        self._cache: "collections.OrderedDict[bytes, Tuple[np.ndarray, np.ndarray]]" = (
            collections.OrderedDict()
        )
        self.cache_hits = 0
        self.cache_misses = 0
        self.async_launches = 0
        self.rejected = 0
        self._queue: Optional["queue_mod.Queue"] = None
        self._worker: Optional[threading.Thread] = None

    def _bind(self, ann: Union[AnnIndex, SegmentedAnnIndex]) -> None:
        """Point the service at a searchable snapshot and derive the
        effective serving knobs.  Called from __init__ and on every
        set_index / refresh swap; the snapshot's epoch in the cache key is
        what keeps previously cached results unreachable."""
        self.ann = ann
        self.index = getattr(ann, "index", ann)  # back-compat alias
        self.config = ann.config
        self._segmented = isinstance(ann, SegmentedAnnIndex)
        # Effective serving knobs: the service config overrides, else the
        # index-level settings (an AnnIndex built/loaded with blockmax_keep
        # or use_kernel serves with them by default).
        if self.scfg.blockmax_keep is not None:
            self._bm_keep = self.scfg.blockmax_keep
            self._bm_block = self.scfg.blockmax_block_size
        else:
            self._bm_keep = getattr(ann, "blockmax_keep", None)
            self._bm_block = getattr(ann, "blockmax_block_size", 256)
        self._uk = (
            self.scfg.use_kernel if self.scfg.use_kernel is not None
            else ann.use_kernel
        )
        if self._segmented:
            if self.mesh is not None:
                raise ValueError(
                    "a segmented snapshot is spread over chips by its writer "
                    "(IndexWriter(shards=N)); mesh= shards a monolithic index"
                )
            if self._bm_keep is not None:
                from repro.core.types import FakeWordsConfig, LexicalLshConfig

                # Segmented blockmax rides the packed superbuffer
                # (docs/DESIGN.md §14); the bm index is built lazily per
                # snapshot inside the packed path, not here.
                if not isinstance(
                    ann.config, (FakeWordsConfig, LexicalLshConfig)
                ):
                    raise ValueError(
                        f"blockmax pruning is not supported for {ann.method}"
                    )
            self._bm = None
            self._search = None
            self._search_filtered = None
            return
        self._bm = None
        if self._bm_keep is not None:
            if not isinstance(ann.index, (FakeWordsIndex, LshIndex)):
                raise ValueError(
                    f"blockmax pruning is not supported for {ann.method}"
                )
            signed = getattr(ann.config, "signed_store", False)
            if self.mesh is not None:
                self._bm = distributed.build_blockmax_sharded(
                    self.mesh, ann.index, self.shard_axes, self._bm_block,
                    signed_store=signed,
                )
            elif ann.bm is not None and ann.bm.block_size == self._bm_block:
                self._bm = ann.bm
            else:
                from repro.core import blockmax

                self._bm = blockmax.build_blockmax(
                    ann.index, self._bm_block, signed_store=signed,
                )
        if self.mesh is not None:
            # The rerank gather must read the store the index was built
            # with: int8 quantized, fp32 originals, or none.
            if ann.quantized_rerank:
                rs = "int8"
            else:
                rs = "exact" if ann.index.vectors is not None else "none"
            # Quantized primary postings change the index spec tree: the
            # sharded search must shard the packed store + scales too.
            pq = getattr(ann.index, "pq", None)
            sharded_args = dict(
                k=self.scfg.k, depth=self.scfg.depth, rerank=self.scfg.rerank,
                use_kernel=self._uk,
                blockmax_keep=self._bm_keep,
                rerank_store=rs,
                postings_bits=pq.bits if pq is not None else 0,
            )
            self._search = distributed.make_sharded_search(
                self.mesh, ann.config, self.shard_axes, **sharded_args
            )
            # The filtered variant takes a trailing doc-sharded bitmap
            # operand (docs/DESIGN.md §13); built eagerly but compiled only
            # on the first filtered query.
            self._search_filtered = distributed.make_sharded_search(
                self.mesh, ann.config, self.shard_axes, filtered=True,
                **sharded_args,
            )
        else:
            self._search = None
            self._search_filtered = None

    # -- online index updates ----------------------------------------------

    def set_index(self, index: Union[AnnIndex, SegmentedAnnIndex]) -> int:
        """Swap the served index for a new snapshot.  Returns the new
        epoch; the epoch-keyed cache makes the old index's cached results
        unreachable (no eviction sweep needed)."""
        if not isinstance(index, (AnnIndex, SegmentedAnnIndex)):
            raise TypeError(
                "set_index takes an AnnIndex or SegmentedAnnIndex"
            )
        with self._lock:
            self._bind(index)
        return self.ann.epoch

    def refresh(self) -> int:
        """Near-real-time visibility: pull the writer's latest snapshot
        (flushing its buffered adds) and serve it.  Returns the serving
        epoch — unchanged when the writer had nothing new, so the result
        cache stays warm across no-op refreshes."""
        if self.writer is None:
            raise ValueError(
                "refresh() needs a service constructed with writer="
            )
        with self._lock:
            self._bind(self.writer.refresh())
        return self.ann.epoch

    # -- serving -----------------------------------------------------------

    def _matcher(self):
        """The effective match stage for single-device serving."""
        return self.ann.matcher_for(self._bm, self._bm_keep)

    # Keying syncs on the tiny encoder output by design — see docstring;
    # only paid when the result cache is on.
    # reprolint: disable=hostsync
    def _cache_key(self, q_rep, q, filt=None) -> bytes:
        """Result-cache key: the encoded query representation's bytes plus
        every knob that changes the result — INCLUDING the index epoch, so
        a swapped/refreshed index can never serve a stale entry.  When
        reranking, the raw normalized queries join the hash — distinct
        queries can collide on a quantized rep (tf row / signature), and
        their exact rerank scores would differ.  A filter bitmap's bytes
        join the hash too (plus a presence flag in the knob tuple, so an
        all-ones mask can never alias the unfiltered entry).  Note
        np.asarray(q_rep) blocks on the (tiny) encoder before the search
        dispatch; that host sync is the price of rep-level keying and only
        paid when the cache is enabled."""
        h = hashlib.sha1(np.asarray(q_rep).tobytes())
        if self.scfg.rerank and q is not None:
            h.update(np.asarray(q).tobytes())
        if filt is not None:
            h.update(np.asarray(filt).tobytes())
        h.update(
            repr((self.scfg.k, self.scfg.depth, self.scfg.rerank,
                  self._bm_keep, self._bm_block, self._uk,
                  getattr(self.ann, "epoch", 0), filt is not None)).encode()
        )
        return h.digest()

    def search_batch(
        self,
        queries: np.ndarray,
        filter: Optional[np.ndarray] = None,
        plan=None,
        reqs: Sequence[Tuple[int, int, float]] = (),
        launch: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(B, dim) -> (scores (B,k), ids (B,k)); pads to max_batch so the
        jit cache holds exactly one entry.

        ``filter``: per-doc predicate bitmap (nonzero = keep) applied
        inside the match stage's single kernel pass (docs/DESIGN.md §13) —
        (N,) shared across the batch, or (B, N) per query (single-device
        and segmented; the sharded path takes the shared (N,) form, which
        shards with the postings).  Segmented indexes take GLOBAL doc ids
        (max_doc space, e.g. from ``ann.global_metadata()``).  Filter bytes
        join the result-cache key, so filtered and unfiltered streams cache
        independently.

        ``plan``: a composed query plan (:mod:`repro.core.plan` —
        FusionStage / MultiVectorPlan / QueryPlan) run as ONE batch in
        place of this service's own index search; sub-plan leaves carry
        their own filters and indexes.  Plan results bypass the result
        cache (a plan's identity isn't hashable state).

        ``reqs`` and ``launch`` come from the async micro-batcher: the
        requests whose rows ``queries`` holds, in row order, as (request
        id, rows, enqueue time), and the id of the batch's first launch.
        They name the launches in a profiler trace and feed
        ``queue_wait_s``."""
        with self._lock:
            return self._search_batch(queries, filter, plan, reqs, launch)

    def _search_batch(
        self,
        queries: np.ndarray,
        filter: Optional[np.ndarray] = None,
        plan=None,
        reqs: Sequence[Tuple[int, int, float]] = (),
        launch: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        b = queries.shape[0]
        if plan is not None:
            if filter is not None:
                raise ValueError(
                    "pass filters on the plan's leaves, not alongside plan="
                )
            lid = next(self._launch_ids) if launch is None else launch
            with obs.span("ann.launch", launch=lid, rows=b):
                t0 = time.perf_counter()
                with obs.span("ann.dispatch", launch=lid):
                    s, ids = plan.run(jnp.asarray(queries))
                # Result hand-off: callers take numpy.
                with obs.span("ann.handoff", launch=lid):
                    s_np, i_np = np.asarray(s), np.asarray(ids)  # reprolint: disable=hostsync
                self.batches += 1
                self._lat.add(time.perf_counter() - t0)
            self.queries_served += b
            return s_np, i_np
        mb = self.scfg.max_batch
        fm = None
        if filter is not None:
            # Host-side caller input (predicate bitmap), not a device array.
            fm = np.asarray(filter)  # reprolint: disable=hostsync
            if fm.ndim == 2 and self.mesh is not None:
                raise ValueError(
                    "sharded filtered serving takes a shared (N,) mask "
                    "(it shards with the postings); per-query (B, N) "
                    "masks are single-device/segmented only"
                )
        use_cache = self.scfg.cache_size > 0
        # First row of each async request: a launch counts the queue wait
        # of the requests whose first row it carries.
        starts = list(itertools.accumulate((r[1] for r in reqs), initial=0))
        charged = 0
        out_s, out_i = [], []
        for i in range(0, b, mb):
            end = min(i + mb, b)
            lid = launch if i == 0 and launch is not None else next(self._launch_ids)
            ids_of_reqs = {}
            if reqs:
                last = bisect.bisect_left(starts, end) - 1
                ids_of_reqs = dict(
                    first_req=reqs[bisect.bisect_right(starts, i) - 1][0],
                    last_req=reqs[last][0],
                )
            with obs.span("ann.launch", launch=lid, rows=end - i, **ids_of_reqs):
                t0 = time.perf_counter()
                if reqs:
                    self.queue_wait_s += sum(t0 - r[2] for r in reqs[charged : last + 1])
                    self.async_requests += last + 1 - charged
                    charged = last + 1
                with obs.span("ann.dispatch", launch=lid):
                    # Padded queries get all-zero mask rows; their padded
                    # (-inf, -1) results are trimmed with the batch below.
                    q_np = _pad_rows(queries[i:end], mb)
                    fl = fm if fm is None or fm.ndim == 1 else _pad_rows(fm[i:end], mb)
                    fl_dev = jnp.asarray(fl) if fl is not None else None
                    if self._segmented:
                        # The segmented reader encodes per search (its
                        # global-stats view owns any fitted model), so key
                        # on the raw query bytes; the epoch in the key
                        # still pins the snapshot.
                        key = self._cache_key(q_np, None, fl) if use_cache else None
                        q = q_rep = None
                    else:
                        q = bruteforce.l2_normalize(jnp.asarray(q_np))
                        q_rep = self.ann.pipeline.encoder(self.ann.index, q)
                        key = self._cache_key(q_rep, q, fl) if use_cache else None
                    hit = self._cache.get(key) if use_cache else None
                    if hit is None:
                        s, ids = self._run_search(q_np, fl_dev, q, q_rep)
                if hit is not None:
                    self._cache.move_to_end(key)
                    s_np, i_np = hit
                    self.cache_hits += 1
                else:
                    # Hand-off point: blocking here keeps device compute
                    # inside the wall time recorded below.
                    with obs.span("ann.handoff", launch=lid):
                        s_np = np.asarray(s)   # reprolint: disable=hostsync
                        i_np = np.asarray(ids)  # reprolint: disable=hostsync
                    if use_cache:
                        self.cache_misses += 1
                        self._cache[key] = (s_np, i_np)
                        while len(self._cache) > self.scfg.cache_size:
                            self._cache.popitem(last=False)
                out_s.append(s_np)
                out_i.append(i_np)
                self.batches += 1
                self._lat.add(time.perf_counter() - t0)
        self.queries_served += b
        return np.concatenate(out_s)[:b], np.concatenate(out_i)[:b]

    def _run_search(self, q_np, fl_dev, q, q_rep):
        """Dispatch one padded chunk through the served index: segmented
        (the reader normalises and encodes), sharded, or single-device."""
        if self._segmented:
            return self.ann.search(
                jnp.asarray(q_np), k=self.scfg.k,
                depth=self.scfg.depth, rerank=self.scfg.rerank,
                use_kernel=self._uk, filter_mask=fl_dev,
                blockmax_keep=self._bm_keep,
                blockmax_block_size=self._bm_block,
            )
        if self._search is not None:
            args = (self.ann.index,) + (
                (self._bm,) if self._bm is not None else ()
            ) + (q_rep, q)
            if fl_dev is not None:
                return self._search_filtered(*args, fl_dev)
            return self._search(*args)
        return pl.match_rerank(
            self._matcher(), self.ann.index, q_rep, q,
            self.scfg.k, self.scfg.depth, self.scfg.rerank,
            bm=self._bm, use_kernel=self._uk,
            reranker=self.ann.pipeline.reranker,
            filt=fl_dev,
        )

    # ``search`` is the public name (filter= / plan= per docs/DESIGN.md
    # §13); ``search_batch`` predates it and stays as the primary def.
    search = search_batch

    # -- async micro-batching loop (docs/DESIGN.md §14) ---------------------

    def start_async(self) -> None:
        """Start the admission queue + micro-batcher worker.  Callers then
        submit single queries through :meth:`search_async`; the worker
        coalesces arrivals into one ``search_batch`` launch once the batch
        reaches ``max_batch`` rows or the oldest request has waited
        ``max_wait_s`` (the SLO's batching window)."""
        if self._worker is not None:
            return
        self._queue = queue_mod.Queue(maxsize=self.scfg.queue_depth)
        self._stop = threading.Event()
        self._worker = threading.Thread(
            target=self._batch_loop, name="ann-batcher", daemon=True
        )
        self._worker.start()

    def stop_async(self, drain: bool = True) -> None:
        """Stop the worker.  ``drain=True`` serves everything already
        admitted first; pending futures are failed otherwise."""
        if self._worker is None:
            return
        if not drain:
            self._stop.set()
        self._queue.put(None)  # wake the worker
        self._worker.join()
        self._worker = None
        # Fail anything still queued (drain=False, or raced past the
        # sentinel) rather than leaving callers blocked forever.
        while True:
            try:
                req = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            if req is not None:
                req[3].set_exception(RuntimeError("service stopped"))
        self._queue = None

    def search_async(
        self, query: np.ndarray, filter: Optional[np.ndarray] = None
    ) -> "Future[Tuple[np.ndarray, np.ndarray]]":
        """Admit one query ((dim,) or (b, dim)) to the micro-batcher;
        resolves to this request's (scores, ids) rows.  Raises
        ``queue.Full`` when the admission queue is at ``queue_depth``
        (backpressure: the caller sheds or retries — queueing deeper would
        only grow everyone's tail latency)."""
        if self._queue is None:
            raise RuntimeError("call start_async() first")
        # Caller-side numpy inputs: coercion + coalescing key are host work.
        q = np.asarray(query)  # reprolint: disable=hostsync
        if q.ndim == 1:
            q = q[None, :]
        fkey = None if filter is None else np.asarray(filter).tobytes()  # reprolint: disable=hostsync
        fut: "Future[Tuple[np.ndarray, np.ndarray]]" = Future()
        rid = next(self._req_ids)
        with obs.span("ann.enqueue", req=rid):
            try:
                self._queue.put_nowait(
                    (q, filter, fkey, fut, time.perf_counter(), rid)
                )
            except queue_mod.Full:
                # Admission counters are bumped from arbitrary caller
                # threads; without the lock, concurrent += drops increments.
                with self._lock:
                    self.rejected += 1
                raise
        return fut

    def _batch_loop(self) -> None:
        carry = None
        while True:
            if carry is None:
                with obs.span("ann.queue_wait"):
                    req = self._queue.get()
            else:
                req, carry = carry, None
            if req is None:
                return
            if self._stop.is_set():
                req[3].set_exception(RuntimeError("service stopped"))
                continue
            with obs.span("ann.coalesce") as coalesce:
                batch = [req]
                rows = req[0].shape[0]
                deadline = req[4] + self.scfg.max_wait_s
                # Coalesce until max_batch rows or the OLDEST request's wait
                # hits the window; only same-filter requests share a launch
                # (one bitmap operand per batch).  Backlog already sitting
                # in the queue coalesces unconditionally (it costs nothing
                # and is what keeps throughput up when arrivals outrun
                # launches); the deadline only governs how long to wait for
                # MORE.
                while rows < self.scfg.max_batch:
                    try:
                        nxt = self._queue.get_nowait()
                    except queue_mod.Empty:
                        wait = deadline - time.perf_counter()
                        if wait <= 0:
                            break
                        try:
                            nxt = self._queue.get(timeout=wait)
                        except queue_mod.Empty:
                            break
                    if nxt is None or self._stop.is_set():
                        carry = nxt
                        break
                    if nxt[2] != req[2]:
                        carry = nxt  # different filter: next launch
                        break
                    batch.append(nxt)
                    rows += nxt[0].shape[0]
                launch = next(self._launch_ids)
                coalesce.set_metadata(launch=launch, rows=rows)
            try:
                qs = np.concatenate([r[0] for r in batch], axis=0)
                s, ids = self.search_batch(
                    qs, filter=req[1], launch=launch,
                    reqs=[(r[5], r[0].shape[0], r[4]) for r in batch],
                )
                with obs.span("ann.resolve", launch=launch):
                    done = time.perf_counter()
                    # Stats are read by caller threads (stats()/
                    # reset_latency() hold the lock); mutate them under it
                    # too.  Future resolution stays OUTSIDE the lock:
                    # set_result runs done-callbacks on this thread, and a
                    # callback that re-enters the service must not find the
                    # lock held.
                    with self._lock:
                        self.async_launches += 1
                        for r in batch:
                            self._req_lat.add(done - r[4])
                    off = 0
                    for r in batch:
                        n = r[0].shape[0]
                        r[3].set_result((s[off : off + n], ids[off : off + n]))
                        off += n
            except Exception as e:  # propagate to every caller in the batch
                for r in batch:
                    if not r[3].done():
                        r[3].set_exception(e)

    def reset_latency(self) -> None:
        """Drop recorded batch latencies (e.g. after a warmup/compile batch,
        whose wall time is orders of magnitude above steady state and would
        otherwise dominate the p99)."""
        with self._lock:
            self._lat.clear()
            self._req_lat.clear()

    @staticmethod
    def _pcts(hist: obs.LatencyHistogram) -> Tuple[Optional[float], Optional[float]]:
        """(p50, p99) in ms, None when nothing was recorded."""
        p50, p99 = hist.percentile(50), hist.percentile(99)
        if p50 is None or p99 is None:
            return None, None
        return round(p50 * 1e3, 3), round(p99 * 1e3, 3)

    def _packed_stats(self) -> dict:
        """Observability for the packed single-launch path: process-wide
        executable-cache counters plus this snapshot's bucket-ladder
        occupancy.  Reports only what is already built — never forces a
        pack (packed state is lazy and stays None until first search)."""
        out = dict(
            (f"exec_cache_{k}", v)
            for k, v in packed_mod.EXEC_CACHE.stats().items()
        )
        pk = getattr(self.ann, "_packed", None)
        shards = getattr(self.ann, "shards", 1)
        out["shards"] = shards
        out["merge_bytes_per_launch"] = distributed.merge_bytes(
            self.scfg.max_batch, self.scfg.depth, shards, self.scfg.rerank)
        out["shard_live_rows"] = None
        if pk is not None:
            # Live rows each chip holds (one entry on one device).
            out["shard_live_rows"] = list(pk.shard_live) or [pk.n_live]
            out["packed_bucket"] = pk.bucket
            # Compiled scratch of the search executable last run on this
            # snapshot: about 0 unless a call relays the corpus out.
            out["packed_search_temp_bytes"] = pk.search_temp_bytes
            out["packed_rows"] = pk.n_rows
            out["packed_live"] = pk.n_live
            out["packed_occupancy"] = round(pk.n_rows / (pk.bucket * pk.shards), 4)
            out["packed_appends"] = pk.appends
        else:
            out["packed_bucket"] = None
            err = getattr(self.ann, "_packed_err", None)
            if err is not None:
                out["packed_unsupported"] = err
        return out

    def stats(self) -> dict:
        # Under the lock: a clear() between reading ``n`` and the counts
        # would put the rank past every bucket.
        with self._lock:
            lat_p50, lat_p99 = self._pcts(self._lat)
            req_p50, req_p99 = self._pcts(self._req_lat)
        return {
            "queries": self.queries_served,
            "batches": self.batches,
            "index_bytes": self.ann.nbytes(),
            "num_docs": self.ann.num_docs,
            "method": self.ann.method,
            "epoch": getattr(self.ann, "epoch", None),
            "segments": getattr(self.ann, "num_segments", None),
            # Per-launch wall times (one max_batch chunk each).
            "lat_p50_ms": lat_p50,
            "lat_p99_ms": lat_p99,
            # Per-REQUEST enqueue->result times on the async path: queue
            # wait + batching window + launch — the number an SLO is
            # written against.
            "req_p50_ms": req_p50,
            "req_p99_ms": req_p99,
            "async_launches": self.async_launches,
            "async_requests": self.async_requests,
            "queue_wait_s": self.queue_wait_s,
            "rejected": self.rejected,
            "queue_depth": self._queue.qsize() if self._queue is not None else 0,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_entries": len(self._cache),
            **self._packed_stats(),
        }
