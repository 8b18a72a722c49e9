"""Lucene-style segmented mutable index: IndexWriter / commit / merge over
immutable AnnIndex segments (docs/DESIGN.md §11).

The paper's whole premise is riding Lucene's native machinery, and the most
Lucene part of Lucene is the segmented index lifecycle that lets a real
deployment ingest documents while serving: immutable segments + sidecar
live-docs bitsets for deletes + generation-numbered commit points +
background merges.  This module reproduces that lifecycle on top of the
staged Build/Search pipelines:

  * :class:`repro.core.index.AnnIndex` is the immutable **segment** unit —
    ``IndexWriter.add`` buffers rows and flushes them through the method's
    :class:`repro.core.builder.BuildPipeline` into a fresh segment; a built
    segment never changes.
  * ``IndexWriter.delete(ids)`` flips bits in a per-segment **liveDocs**
    mask (Lucene's ``.liv`` sidecar).  Deleted docs are masked to
    ``(-inf, -1)`` *inside the match stage*
    (:class:`repro.core.pipeline.LiveDocsMatcher`), not post-filtered, so
    ``depth`` semantics survive deletes exactly.
  * ``IndexWriter.commit`` atomically persists a generation-numbered commit
    point: per-segment v1 index dirs + per-generation live files + a
    ``segments_N.json`` manifest written last via ``os.replace``
    (``format_version: 2``; a plain v1 ``AnnIndex.save`` dir loads as a
    single-segment index for read-compat).
  * A tiered :class:`TieredMergePolicy` compacts small adjacent segments by
    rebuilding their live rows through the same BuildPipeline stages —
    deleted rows drop out and global doc ids remap, exactly like a Lucene
    merge.
  * :class:`SegmentedAnnIndex` is the point-in-time **reader**:
    multi-segment search runs the method's jit'd matcher per segment and
    merges per-segment top-k on global ids — the same fan-out/merge
    architecture ``core/distributed.py`` uses across shards, here across
    segments.
  * ``IndexWriter.refresh()`` is the NRT reader hook: flush + snapshot, and
    every visible mutation advances the snapshot **epoch**
    (:func:`repro.core.types.next_epoch`) — the serving layer's
    cache-invalidation key (``serve/ann_service.py``).

**Exact global-statistics scoring.**  Lucene's IndexSearcher scores every
leaf with collection-level statistics; we do the same so a segmented search
is *bitwise identical* to a monolithic build of the equivalent live corpus:

  * fake words — document frequency is recounted over live rows per segment
    and summed (exact integer sum); idf and the classic ``scored`` matrix
    are re-derived per segment from the global (df, live-N) through the
    same :func:`repro.core.builder.classic_scored` formula the build stage
    evaluates (row-local, so bitwise);
  * k-d tree — the reduction refits on the concatenated live originals
    (the one encoding whose "statistic" is a fitted model) and every
    segment's rows re-project through the shared model (row-local matmuls,
    so bitwise);
  * lexical LSH / brute force — signatures and unit vectors carry no
    collection statistics.

Stats views rebuild lazily per snapshot (Lucene rebuilds per-leaf scorers
per reader the same way); ``global_stats=False`` trades exact parity for
per-segment statistics with no refresh cost.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import bruteforce, builder, distributed, pca
from repro.core import index as index_mod
from repro.core import packed as packed_mod
from repro.core import pipeline as pl
from repro.core.index import AnnIndex, AnyConfig
from repro.core.types import (
    BruteForceConfig,
    DocMetadata,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
    SearchParams,
    next_epoch,
)

SEGMENTS_FORMAT_VERSION = 2

_METHOD_BY_CONFIG = {v: k for k, v in index_mod._CONFIG_BY_METHOD.items()}

_COMMIT_RE = re.compile(r"^segments_(\d+)\.json$")

_NEEDS_VECTORS_MSG = (
    "requires the fp32 original vectors on every segment "
    "(rerank_store='exact')"
)

#: Packed single-launch segmented search (docs/DESIGN.md §14) is the
#: default serving path; REPRO_PACKED=0 flips the default back to the
#: per-segment reference loop (search(packed=...) overrides per call).
_PACKED_DEFAULT = os.environ.get("REPRO_PACKED", "1").lower() not in (
    "0", "false", "off",
)


def find_commits(path: str) -> List[Tuple[int, str]]:
    """(generation, filename) for every commit point under ``path``,
    ascending.  Empty when the directory holds no segmented commits (e.g. a
    v1 single-index save, or nothing at all)."""
    if not os.path.isdir(path):
        return []
    out = []
    for name in os.listdir(path):
        m = _COMMIT_RE.match(name)
        if m:
            out.append((int(m.group(1)), name))
    return sorted(out)


def _bucket(n: int) -> int:
    """Round a deleted-doc count up to the next power of two so the
    FilterMask's static depth inflation doesn't recompile per delete."""
    return 0 if n <= 0 else 1 << (n - 1).bit_length()


def _concat_metadata(
    parts: Sequence[Optional[DocMetadata]], rows_kept=None
) -> Optional[DocMetadata]:
    """Concatenate per-chunk metadata (flush: buffered adds; merge: the
    merged segments' live rows via ``rows_kept`` boolean selectors).  All
    chunks must agree on presence and field set — metadata over part of a
    segment cannot answer a predicate over all of it."""
    parts = list(parts)
    if all(p is None for p in parts):
        return None
    if any(p is None for p in parts):
        raise ValueError(
            "metadata must cover either all rows or none (some adds/"
            "segments carry metadata and some do not)"
        )
    names = parts[0].field_names
    if any(p.field_names != names for p in parts):
        raise ValueError(
            f"inconsistent metadata fields: {[p.field_names for p in parts]}"
        )
    if rows_kept is None:
        vals = [np.asarray(p.values) for p in parts]
    else:
        vals = [np.asarray(p.values)[k] for p, k in zip(parts, rows_kept)]
    return DocMetadata(
        values=jnp.asarray(np.concatenate(vals, axis=0)), field_names=names
    )


# --------------------------------------------------------------------------
# Segments
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Segment:
    """One immutable index + its mutable sidecar live-docs mask.

    ``ann`` never changes after build (the Lucene segment invariant); all
    mutation is bit-flips in ``live`` (True = live).  ``name`` is the
    stable on-disk directory name assigned at flush time.

    ``source`` holds the unit-normalized original rows host-side when the
    index itself does not carry them (rerank_store "int8"/"none"): merges
    rebuild from these and the kd-tree's global-stats refit reads them, so
    the writer no longer forces rerank_store="exact".  None when
    ``ann.index.vectors`` is present (no duplicate copy) — read through
    :meth:`source_rows`.  Persisted once per segment as ``source.npz``.
    """

    ann: AnnIndex
    live: np.ndarray
    name: str
    source: Optional[np.ndarray] = None
    # Doc-sharded segments (``IndexWriter(shards=N)``): the docs on each
    # chip, in doc order.  Chip s stores its docs first in its block of
    # ``ann.num_docs // N`` rows and zero pad rows after them.
    shard_rows: Optional[Tuple[int, ...]] = None

    def stored_rows(self) -> Optional[np.ndarray]:
        """Stored row of each doc of a doc-sharded segment; None where the
        segment stores doc j at row j."""
        if self.shard_rows is None:
            return None
        block = self.ann.num_docs // len(self.shard_rows)
        return np.concatenate(
            [s * block + np.arange(n) for s, n in enumerate(self.shard_rows)]
        )

    def source_rows(self) -> Optional[np.ndarray]:
        """Unit-normalized original rows (merge/refit operand), whichever
        store carries them, in doc order; None if the segment kept
        neither."""
        if self.ann.index.vectors is not None:
            rows = np.asarray(self.ann.index.vectors)
            stored = self.stored_rows()
            return rows if stored is None else rows[stored]
        return self.source

    @property
    def num_docs(self) -> int:
        """Total docs, deleted included (Lucene maxDoc)."""
        if self.shard_rows is not None:
            return sum(self.shard_rows)
        return self.ann.num_docs

    @property
    def num_live(self) -> int:
        return int(self.live.sum())

    @property
    def del_count(self) -> int:
        return self.num_docs - self.num_live

    def snapshot(self) -> "Segment":
        """Point-in-time copy: shares the immutable index, copies the
        mutable live mask — later writer deletes don't leak into an open
        reader."""
        return Segment(
            ann=self.ann, live=self.live.copy(), name=self.name,
            source=self.source, shard_rows=self.shard_rows,
        )


# --------------------------------------------------------------------------
# Merge policy
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TieredMergePolicy:
    """Lucene-style tiered merging over ADJACENT segments.

    Segments land in exponential size tiers (tier t holds up to
    ``floor_docs * merge_factor**t`` live docs); a run of ``merge_factor``
    adjacent same-tier segments merges into one segment of the next tier,
    so the segment count stays O(merge_factor * log(N / floor_docs)) under
    a steady add stream.  A segment whose delete ratio reaches
    ``expunge_ratio`` is rewritten alone (deletes drop out).  Only adjacent
    runs merge: unlike Lucene we guarantee global doc order == add order,
    which is what makes segmented search results identical to a monolithic
    build of the live corpus.
    """

    merge_factor: int = 8
    floor_docs: int = 1024
    expunge_ratio: float = 0.5

    def __post_init__(self):
        if self.merge_factor < 2:
            raise ValueError("merge_factor must be >= 2")
        if not (0.0 < self.expunge_ratio <= 1.0):
            raise ValueError("expunge_ratio must be in (0, 1]")

    def tier(self, num_live: int) -> int:
        t, cap = 0, max(1, self.floor_docs)
        while num_live > cap:
            cap *= self.merge_factor
            t += 1
        return t

    def find_merge(self, segments: Sequence[Segment]) -> Optional[Tuple[int, int]]:
        """The next ``[start, end)`` range to merge, or None when the
        geometry is stable.  Called in a loop by ``IndexWriter``."""
        for i, seg in enumerate(segments):
            if seg.num_docs and seg.del_count / seg.num_docs >= self.expunge_ratio:
                return (i, i + 1)
        tiers = [self.tier(s.num_live) for s in segments]
        start = 0
        while start < len(tiers):
            end = start
            while end < len(tiers) and tiers[end] == tiers[start]:
                end += 1
            if end - start >= self.merge_factor:
                return (start, start + self.merge_factor)
            start = end
        return None


# --------------------------------------------------------------------------
# Per-segment search (jit'd per segment, merged on global ids)
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("matcher", "depth", "use_kernel", "native")
)
def _segment_match(
    matcher: pl.FilterMask,
    view,
    live: jax.Array,
    base: jax.Array,
    q_rep: jax.Array,
    depth: int,
    use_kernel: Optional[bool],
    native: bool = False,
):
    """One segment's contribution: mask-restricted match (the method's own
    matcher stage inside a FilterMask) on global ids.  ``native=False`` is
    the historical deletes path (depth inflation + re-reduce, bitwise what
    shipped); ``native=True`` threads the mask into the score stage as the
    kernels' in-tile filter operand — ONE kernel pass, used whenever a
    predicate bitmap is composed in (docs/DESIGN.md §13)."""
    s, i = matcher(view, q_rep, depth, live, use_kernel=use_kernel, native=native)
    return s, jnp.where(i >= 0, i + base, -1)


@functools.partial(
    jax.jit, static_argnames=("k", "depth", "rerank", "quantized", "bases")
)
def _merge_candidates(
    parts_s,
    parts_i,
    q_norm,
    stores,
    k: int,
    depth: int,
    rerank: bool,
    quantized: bool,
    bases: Tuple[int, ...],
):
    """Merge per-segment candidate lists exactly like the monolithic path:
    global top-``depth`` by MATCH score first (so the rerank sees precisely
    the candidate set a monolithic depth-d match would produce), then the
    rerank over the merged list.  Segment-major concatenation +
    ``lax.top_k``'s stable ties reproduce the lowest-global-id tie-break
    bit-for-bit.

    The rerank assembles the merged candidates' stored rows into ONE
    ``(B, depth, dim)`` tensor — each segment contributes its owned
    positions — and runs the same einsum as the monolithic reranker.
    Unlike the distributed path's local-rerank-then-merge (which avoids
    cross-shard vector movement), segments share a process, and scoring in
    the merged candidate positions is what makes the rerank scores bitwise
    equal to a monolithic build (XLA's reduction for a gathered-candidate
    dot is position-dependent at the last bit)."""
    all_s = jnp.concatenate(parts_s, axis=1)
    all_i = jnp.concatenate(parts_i, axis=1)
    top_s, pos = jax.lax.top_k(all_s, depth)
    top_i = jnp.take_along_axis(all_i, pos, axis=-1)
    if not rerank:
        return top_s[:, :k], top_i[:, :k]
    cand = scale = None
    for base, store in zip(bases, stores):
        rows = store[0] if quantized else store
        n = rows.shape[0]
        own = (top_i >= base) & (top_i < base + n)
        safe = jnp.clip(top_i - base, 0, n - 1)
        part = rows[safe]  # (B, depth, dim)
        cand = part if cand is None else jnp.where(own[:, :, None], part, cand)
        if quantized:
            sc = store[1][safe]  # (B, depth)
            scale = sc if scale is None else jnp.where(own, sc, scale)
    s = jnp.einsum("bd,bcd->bc", q_norm, cand.astype(jnp.float32))
    if quantized:
        s = s * scale
    s = jnp.where(top_i >= 0, s, -jnp.inf)
    out_s, p2 = jax.lax.top_k(s, k)
    return out_s, jnp.take_along_axis(top_i, p2, axis=-1)


# --------------------------------------------------------------------------
# The reader
# --------------------------------------------------------------------------


class SegmentedAnnIndex:
    """Point-in-time multi-segment reader (Lucene DirectoryReader).

    Immutable snapshot: segments share their (immutable) per-segment
    AnnIndexes with the writer but own copies of the live masks, and
    ``epoch`` identifies the snapshot for cache invalidation.  Search fans
    out the method's matcher per segment (deleted docs masked inside the
    match stage) and merges per-segment top-k on global ids — the shard
    fan-out/merge architecture of ``core/distributed.py``, across segments.

    Doc ids are segment-stable: global id = segment base (sum of preceding
    segments' row counts, deleted included) + local row.  Ids survive
    deletes; merges compact and remap them (like Lucene).

    With ``mesh`` (a doc-sharded writer's snapshot) every segment's rows
    are spread over the mesh's chips, and search is one launch across them
    over per-chip packed views with the global top-``depth`` merge
    (``packed.packed_search`` on a doc-sharded view): the ids and answers
    of one device over the same rows, filters included.
    """

    def __init__(
        self,
        config: AnyConfig,
        segments: Sequence[Segment],
        use_kernel: Optional[bool] = None,
        global_stats: bool = True,
        epoch: Optional[int] = None,
        mesh=None,
    ):
        if isinstance(config, KdTreeConfig) and config.backend == "tree":
            raise ValueError(
                "segmented kd-tree requires backend='scan' (identical "
                "results, docs/DESIGN.md §3); the host-built tree arrays "
                "cannot re-derive shared global statistics"
            )
        self.config = config
        self.segments = list(segments)
        self.use_kernel = use_kernel
        self.global_stats = global_stats
        self.mesh = mesh
        self.epoch = next_epoch() if epoch is None else epoch
        self.pipeline = pl.build_pipeline(config)
        # Quantized rerank iff every segment carries ONLY the int8 store
        # (writer segments built with rerank_store="int8", or v1
        # read-compat of a monolithic int8-rerank index).
        self.quantized_rerank = bool(self.segments) and all(
            s.ann.index.vectors is None and s.ann.index.vq is not None
            for s in self.segments
        )
        self._views: Optional[List[Any]] = None
        self._live_dev: Optional[List[jax.Array]] = None
        self._n_live = int(sum(s.num_live for s in self.segments))
        # Packed single-launch state (docs/DESIGN.md §14): built lazily;
        # _packed_prior is the previous snapshot's pack, handed over by
        # IndexWriter.refresh() so append-only refreshes can absorb it via
        # a donated incremental repack instead of re-concatenating.
        self._packed: Optional[packed_mod.PackedSegments] = None
        self._packed_prior: Optional[packed_mod.PackedSegments] = None
        self._packed_err: Optional[str] = None

    # -- shape/identity ----------------------------------------------------

    @property
    def method(self) -> str:
        return _METHOD_BY_CONFIG[type(self.config)]

    @property
    def num_docs(self) -> int:
        """LIVE docs (Lucene ``numDocs``); ``max_doc`` counts deleted too."""
        return self._n_live

    @property
    def max_doc(self) -> int:
        return sum(s.num_docs for s in self.segments)

    @property
    def num_segments(self) -> int:
        return len(self.segments)

    @property
    def shards(self) -> int:
        """Chips the snapshot's rows are spread over (1 on one device)."""
        return 1 if self.mesh is None else self.mesh.size

    @property
    def del_count(self) -> int:
        return self.max_doc - self._n_live

    def nbytes(self) -> int:
        return sum(s.ann.nbytes() + s.live.nbytes for s in self.segments)

    def live_global_ids(self) -> np.ndarray:
        """Stable global ids of the live docs in corpus (add) order — the
        id mapping between this reader and a monolithic build of the
        equivalent live corpus (monolithic id j <-> live_global_ids()[j])."""
        parts, base = [], 0
        for s in self.segments:
            parts.append(np.flatnonzero(s.live) + base)
            base += s.num_docs
        return (
            np.concatenate(parts) if parts else np.zeros((0,), np.int64)
        ).astype(np.int64)

    # -- global collection statistics (Lucene IndexSearcher-level) ---------

    def _ensure_views(self) -> Tuple[List[Any], List[pl.FilterMask]]:
        if self._views is None:
            self._live_dev = [self._live_operand(s) for s in self.segments]
            self._views = (
                self._stat_views() if self.global_stats
                else [s.ann.index for s in self.segments]
            )
        base = pl.make_matcher(self.config)
        if self.global_stats and isinstance(base, pl.FakeWordsMatcher):
            base = dataclasses.replace(base, df_num_docs=self._n_live)
        matchers = [
            pl.FilterMask(inner=base, extra=_bucket(s.del_count))
            for s in self.segments
        ]
        return self._views, matchers

    def _live_operand(self, seg: Segment) -> jax.Array:
        """A segment's live mask on the device, laid out like its rows: on
        one device as it is, doc-sharded with pad rows dead on a mesh."""
        stored = seg.stored_rows()
        if stored is None:
            return jnp.asarray(seg.live)
        live = np.zeros(seg.ann.num_docs, bool)
        live[stored] = seg.live
        return jax.device_put(
            live, NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
        )

    # -- packed single-launch path (docs/DESIGN.md §14) ---------------------

    def packed_segments(self) -> Optional[packed_mod.PackedSegments]:
        """This snapshot's packed superbuffer, built lazily and cached on
        the reader.  None when the layout cannot ride the single-launch
        path (mixed store presence, per-segment statistics, ...) — the
        reason is kept in ``_packed_err`` and search falls back to the
        per-segment loop."""
        if self._packed is not None:
            return self._packed
        if self._packed_err is not None:
            return None
        views, _ = self._ensure_views()
        if self.mesh is not None:
            self._packed = packed_mod.pack_segments_sharded(
                self.config, views, self.segments, self.mesh
            )
            return self._packed
        prior, self._packed_prior = self._packed_prior, None
        try:
            self._packed = packed_mod.pack_segments(
                self.config, views, self.segments, self.global_stats,
                prior=prior,
            )
        except packed_mod.PackedUnsupported as e:
            self._packed_err = str(e)
            return None
        return self._packed

    def _packed_matcher(self):
        base = pl.make_matcher(self.config)
        if self.global_stats and isinstance(base, pl.FakeWordsMatcher):
            # df_max_ratio >= 1 keeps every term regardless of collection
            # size, so df_num_docs stays unset and the matcher's static
            # identity survives refreshes (zero recompiles per cycle).  A
            # real prune ratio needs the live count for parity with the
            # loop and accepts a recompile when it changes.
            if base.df_max_ratio < 1.0:
                base = dataclasses.replace(base, df_num_docs=self._n_live)
        return base

    # -- metadata (predicate source for filtered search) --------------------

    def global_metadata(self) -> Optional[DocMetadata]:
        """The segments' per-doc metadata concatenated in global-id order
        (deleted rows included, so row g answers for global doc id g) —
        build predicate bitmaps from it and pass them to
        ``search(filter_mask=)``.  None when no segment carries metadata;
        mixed coverage raises (a predicate over half the corpus is a bug)."""
        mds = [s.ann.metadata for s in self.segments]
        if all(md is None for md in mds):
            return None
        if any(md is None for md in mds):
            raise ValueError(
                "some segments carry doc metadata and some do not; "
                "metadata-filtered search needs every segment covered"
            )
        names = mds[0].field_names
        if any(md.field_names != names for md in mds):
            raise ValueError(
                f"segments carry inconsistent metadata fields: "
                f"{[md.field_names for md in mds]}"
            )
        return DocMetadata(
            values=jnp.concatenate([md.values for md in mds], axis=0),
            field_names=names,
        )

    def _stat_views(self) -> List[Any]:
        segs = self.segments
        if isinstance(self.config, FakeWordsConfig):
            df = None
            for s, live in zip(segs, self._live_dev):
                # dot-int4 packed tf away: its df freezes at the build-time
                # count (Lucene-style) until a merge rebuilds the segment.
                d = (
                    builder.live_df(s.ann.index.tf, live)
                    if s.ann.index.tf is not None else s.ann.index.df
                )
                df = d if df is None else df + d
            idf = builder.idf_from_df(df, self._n_live)
            views = []
            for s in segs:
                idx = s.ann.index
                if self.config.scoring != "classic":
                    views.append(dataclasses.replace(idx, df=df, idf=idf))
                    continue
                scored = builder.classic_scored(idx.tf, idf, idx.norm)
                if idx.pq is not None:
                    # Quantized-classic keeps tf precisely for this: rebuild
                    # scores under GLOBAL stats, then re-quantize row-locally
                    # — each row's scale/codes depend only on that row, so a
                    # segment view is bitwise the monolithic quantized build.
                    views.append(dataclasses.replace(
                        idx, df=df, idf=idf, scored=None,
                        pq=builder.quantize_postings(
                            scored, idx.pq.bits, idx.pq.group or 32
                        ),
                    ))
                else:
                    views.append(
                        dataclasses.replace(idx, df=df, idf=idf, scored=scored)
                    )
            return views
        if isinstance(self.config, KdTreeConfig):
            if any(s.source_rows() is None for s in segs):
                raise ValueError(
                    "global-stats refresh for a segmented kd-tree "
                    + _NEEDS_VECTORS_MSG
                    + " or a source sidecar; pass global_stats=False to "
                    "score each segment under its own fitted reduction"
                )
            from repro.kernels.fused_topk import ops as fused

            live_rows = [s.source_rows()[s.live] for s in segs]
            v_live = jnp.asarray(np.concatenate(live_rows, axis=0))
            model, _ = pca.fit_reduction(
                v_live, self.config.dims, self.config.reduction,
                self.config.ppa_remove,
            )
            views = []
            for s in segs:
                red = pca.apply_reduction(
                    model, jnp.asarray(s.source_rows())
                ).astype(jnp.float32)
                views.append(
                    dataclasses.replace(
                        s.ann.index, reduced=red, reduction=model,
                        lifted=fused.lift_l2(red),
                    )
                )
            return views
        # LSH signatures and brute-force unit vectors carry no collection
        # statistics: the stored index IS the view.
        return [s.ann.index for s in segs]

    # -- search ------------------------------------------------------------

    def encode_queries(self, queries: jax.Array) -> jax.Array:
        views, _ = self._ensure_views()
        if not views:
            raise ValueError("cannot encode against an empty segmented index")
        return self.pipeline.encode(views[0], queries)

    def search(
        self,
        queries: jax.Array,
        k: int = 10,
        depth: int = 100,
        rerank: bool = False,
        params: Optional[SearchParams] = None,
        use_kernel: Optional[bool] = None,
        filter_mask: Optional[jax.Array] = None,
        packed: Optional[bool] = None,
        blockmax_keep: Optional[int] = None,
        blockmax_block_size: int = 256,
    ) -> Tuple[jax.Array, jax.Array]:
        """Multi-segment staged search: encode once (the global-stats view
        carries any fitted model) -> per-segment live-masked match [+ local
        rerank gather] -> merge on global ids.  Same signature and — for a
        healthy snapshot — bitwise the same results as ``AnnIndex.search``
        over the equivalent live corpus (ids mapped through
        :meth:`live_global_ids`).

        ``filter_mask`` ((max_doc,) or (B, max_doc), nonzero = keep,
        indexed by GLOBAL doc id — e.g. built from
        :meth:`global_metadata`): each segment slices its own rows,
        composes liveDocs ∧ predicate into ONE mask, and runs a single
        in-kernel filtered pass (docs/DESIGN.md §13).  A mask that filters
        every doc returns padded (-inf, -1) rows, never NaNs.

        ``packed`` selects the single-launch path over the packed
        superbuffer (docs/DESIGN.md §14): None follows the process default
        (on unless REPRO_PACKED=0, falling back silently to the loop for
        unsupported layouts), True raises when unsupported, False forces
        the per-segment reference loop.  ``blockmax_keep`` enables
        two-stage blockmax pruning over the packed view (fake-words and
        LSH encodings; approximate by design, docs/DESIGN.md §6)."""
        p = params if params is not None else SearchParams(k=k, depth=depth, rerank=rerank)
        if self._n_live == 0:
            raise ValueError("segmented index has no live docs to search")
        uk = self.use_kernel if use_kernel is None else use_kernel
        views, matchers = self._ensure_views()
        q_norm = bruteforce.l2_normalize(jnp.asarray(queries))
        fm = None
        if filter_mask is not None:
            fm = jnp.asarray(filter_mask)
            if fm.shape[-1] != self.max_doc:
                raise ValueError(
                    f"filter_mask covers {fm.shape[-1]} docs but the index "
                    f"has max_doc={self.max_doc} (masks index GLOBAL ids, "
                    "deleted rows included)"
                )
        if self.mesh is not None and (blockmax_keep is not None or packed is False):
            raise ValueError(
                "a doc-sharded snapshot serves the packed single launch "
                "only: no blockmax_keep or packed=False"
            )
        want_packed = self.mesh is not None or (
            _PACKED_DEFAULT if packed is None else bool(packed)
        )
        if blockmax_keep is not None and not want_packed:
            raise ValueError(
                "blockmax_keep rides the packed single-launch path; "
                "packed=False forces the per-segment reference loop"
            )
        if want_packed:
            pk = self.packed_segments()
            if pk is None:
                if packed or blockmax_keep is not None:
                    raise ValueError(
                        "packed single-launch path unavailable for this "
                        f"snapshot: {self._packed_err}"
                    )
                # default-on: serve via the per-segment reference loop
            else:
                if p.rerank and not self.quantized_rerank and (
                    pk.view.vectors is None
                ):
                    raise ValueError(
                        "rerank=True " + _NEEDS_VECTORS_MSG
                        + " or the int8 store on every segment"
                    )
                bm = None
                if blockmax_keep is not None:
                    if not isinstance(
                        self.config, (FakeWordsConfig, LexicalLshConfig)
                    ):
                        raise ValueError(
                            "blockmax pruning supports fake-words and LSH "
                            "encodings only (docs/DESIGN.md §6)"
                        )
                    bm = packed_mod.packed_blockmax(
                        pk, self.config, blockmax_block_size
                    )
                return packed_mod.packed_search(
                    pk, self.pipeline, self._packed_matcher(), q_norm,
                    p.k, p.depth, rerank=p.rerank,
                    quantized=self.quantized_rerank, use_kernel=uk,
                    fm=fm, n_keep=blockmax_keep, bm=bm,
                )
        q_rep = self.pipeline.encoder(views[0], q_norm)
        d_eff = min(p.depth, self._n_live)
        k_eff = min(p.k, d_eff)
        parts_s, parts_i, stores, bases = [], [], [], []
        base = 0
        for seg, view, live, matcher in zip(
            self.segments, views, self._live_dev, matchers
        ):
            if fm is None:
                seg_mask, native = live, False
            else:
                pred = fm[..., base : base + seg.num_docs] != 0
                seg_mask = pred & (live if pred.ndim == 1 else live[None, :])
                native = True
            s, gid = _segment_match(
                matcher, view, seg_mask, jnp.int32(base), q_rep, p.depth, uk,
                native=native,
            )
            parts_s.append(s)
            parts_i.append(gid)
            bases.append(base)
            base += seg.num_docs
            if p.rerank:
                idx = seg.ann.index
                if self.quantized_rerank:
                    stores.append((idx.vq.q, idx.vq.scale))
                elif idx.vectors is not None:
                    stores.append(idx.vectors)
                else:
                    raise ValueError(
                        "rerank=True " + _NEEDS_VECTORS_MSG
                        + " or the int8 store on every segment"
                    )
        return _merge_candidates(
            tuple(parts_s), tuple(parts_i), q_norm, tuple(stores),
            k_eff, d_eff, p.rerank, self.quantized_rerank, tuple(bases),
        )

    # -- persistence (read side; IndexWriter.commit writes) ----------------

    @classmethod
    def load(
        cls,
        path: str,
        generation: Optional[int] = None,
        **overrides,
    ) -> "SegmentedAnnIndex":
        """Open a commit point (latest generation by default).  A plain v1
        ``AnnIndex.save`` directory loads as a single fully-live segment
        (read-compat), so every pre-segmentation index remains servable."""
        commits = find_commits(path)
        if not commits:
            if os.path.exists(os.path.join(path, "config.json")):
                if generation is not None:
                    raise FileNotFoundError(
                        f"{path!r} is a v1 single-index save with no commit "
                        f"generations; cannot load generation {generation}"
                    )
                ann = AnnIndex.load(path)
                seg = Segment(
                    ann=ann,
                    live=np.ones(ann.num_docs, bool),
                    name="seg0",
                )
                return cls(
                    ann.config, [seg],
                    use_kernel=overrides.get("use_kernel", ann.use_kernel),
                    global_stats=overrides.get("global_stats", True),
                )
            raise FileNotFoundError(
                f"no segments_N.json commit point (and no v1 config.json) "
                f"under {path!r}"
            )
        if generation is None:
            generation, fname = commits[-1]
        else:
            by_gen = dict(commits)
            if generation not in by_gen:
                raise FileNotFoundError(
                    f"no commit generation {generation} under {path!r} "
                    f"(have {sorted(by_gen)})"
                )
            fname = by_gen[generation]
        with open(os.path.join(path, fname)) as f:
            meta = json.load(f)
        version = meta.get("format_version", 2)
        if version > SEGMENTS_FORMAT_VERSION:
            raise ValueError(
                f"commit point {fname!r} has format_version {version}, but "
                f"this build reads <= {SEGMENTS_FORMAT_VERSION} — it was "
                "written by a newer version of the code; upgrade to load it"
            )
        config = index_mod._config_from_json(meta["method"], meta["config"])
        segments = []
        for e in meta["segments"]:
            ann = AnnIndex.load(os.path.join(path, e["name"]))
            if e.get("live_file"):
                with np.load(os.path.join(path, e["live_file"])) as z:
                    live = z["live"].astype(bool)
            else:
                live = np.ones(ann.num_docs, bool)
            source = None
            src_file = os.path.join(path, e["name"], "source.npz")
            if ann.index.vectors is None and os.path.exists(src_file):
                with np.load(src_file) as z:
                    source = z["source"]
            segments.append(
                Segment(ann=ann, live=live, name=e["name"], source=source)
            )
        return cls(
            config, segments,
            use_kernel=overrides.get("use_kernel", meta.get("use_kernel")),
            global_stats=overrides.get(
                "global_stats", meta.get("global_stats", True)
            ),
        )


# --------------------------------------------------------------------------
# The writer
# --------------------------------------------------------------------------


class IndexWriter:
    """Lucene IndexWriter for AnnIndex segments: buffer adds, flush through
    the BuildPipeline, flip liveDocs bits on delete, merge by policy, and
    atomically commit generation-numbered points.

    Doc ids: ``add`` assigns consecutive global ids (segment base + row).
    Ids are stable across adds and deletes; a merge compacts its range and
    REMAPS every id after it (exactly Lucene's contract).  ``refresh()``
    returns a point-in-time :class:`SegmentedAnnIndex` whose ``epoch``
    advances only when something actually changed — an unchanged refresh
    returns the same snapshot, so serving caches stay warm.

    Any ``rerank_store`` ("exact" | "int8" | "none") and any
    ``primary_postings`` ("fp32" | "int8" | "int4") work: when the built
    segment does not carry the fp32 originals, the writer keeps them as a
    host-side ``Segment.source`` sidecar (normalized once, persisted as
    ``source.npz``), so merges still rebuild live rows bit-for-bit and the
    kd-tree's global-stats refit still reads them.

    ``shards=N`` spreads the collection over the first N local devices
    (a 1-D mesh): each flush splits its rows into N contiguous parts that go
    from the host straight to their chip and builds them there through the
    sharded BuildPipeline stages, so no device holds a whole flush; global
    df is summed over chips and segments as on one device.  Snapshots
    serve through one launch across the chips (:class:`SegmentedAnnIndex`
    with ``mesh``).  Fake words, lexical LSH and brute force, with every
    rerank store and postings format; deletes, filters, refresh and merges
    work as on one device, while metadata and commit points are one-device
    only.
    """

    def __init__(
        self,
        config: AnyConfig,
        path: Optional[str] = None,
        rerank_store: str = "exact",
        use_kernel: Optional[bool] = None,
        merge_policy: Optional[TieredMergePolicy] = TieredMergePolicy(),
        max_buffered_docs: Optional[int] = None,
        global_stats: bool = True,
        primary_postings: str = "fp32",
        postings_group: int = 32,
        shards: int = 1,
    ):
        if rerank_store not in ("exact", "int8", "none"):
            raise ValueError(f"unknown rerank_store {rerank_store!r}")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > 1 and not (
            isinstance(config, (FakeWordsConfig, LexicalLshConfig, BruteForceConfig))
            and global_stats
        ):
            raise ValueError(
                "shards > 1 serves fake words, lexical LSH or brute force "
                "with global_stats=True"
            )
        if isinstance(config, KdTreeConfig) and config.backend == "tree":
            raise ValueError(
                "segmented kd-tree requires backend='scan' "
                "(docs/DESIGN.md §3/§11)"
            )
        self.config = config
        self.path = path
        self.rerank_store = rerank_store
        self.primary_postings = primary_postings
        self.postings_group = postings_group
        self.use_kernel = use_kernel
        self.merge_policy = merge_policy
        self.max_buffered_docs = max_buffered_docs
        self.global_stats = global_stats
        self.shards = shards
        self.mesh = distributed.device_mesh(shards) if shards > 1 else None
        self._segments: List[Segment] = []
        self._buf: List[np.ndarray] = []
        self._buf_live: List[np.ndarray] = []
        self._buf_md: List[Optional[DocMetadata]] = []
        self._seg_counter = 0
        self._changed = False
        self._reader: Optional[SegmentedAnnIndex] = None
        # Latest commit generation THIS writer has read or written.  The
        # commit-lineage guard (Lucene's write.lock analog): committing
        # into a directory whose commits this writer never saw would reuse
        # segment names against another writer's dirs.
        self._last_gen = 0

    @classmethod
    def open(cls, path: str, **kwargs) -> "IndexWriter":
        """Open the latest commit point under ``path`` for further writes
        (a plain v1 ``AnnIndex.save`` dir opens as one segment: the upgrade
        path from a frozen index to an online one)."""
        if kwargs.get("shards", 1) > 1:
            raise ValueError("commit points are one-device only (shards=1)")
        reader = SegmentedAnnIndex.load(path)
        kwargs.setdefault("use_kernel", reader.use_kernel)
        kwargs.setdefault("global_stats", reader.global_stats)
        if reader.segments:
            # Continue the store choice the existing segments were built
            # with, so new flushes/merges stay homogeneous.
            idx = reader.segments[0].ann.index
            if idx.vectors is not None:
                kwargs.setdefault("rerank_store", "exact")
            elif getattr(idx, "vq", None) is not None:
                kwargs.setdefault("rerank_store", "int8")
            else:
                kwargs.setdefault("rerank_store", "none")
            pq = getattr(idx, "pq", None)
            if pq is not None:
                kwargs.setdefault("primary_postings", f"int{pq.bits}")
                kwargs.setdefault("postings_group", pq.group or 32)
        w = cls(reader.config, path=path, **kwargs)
        w._segments = reader.segments
        commits = find_commits(path)
        w._last_gen = commits[-1][0] if commits else 0
        nums = [
            int(m.group(1))
            for m in (re.match(r"^seg(\d+)$", s.name) for s in w._segments)
            if m
        ]
        w._seg_counter = max(nums) + 1 if nums else 0
        return w

    # -- counts ------------------------------------------------------------

    @property
    def buffered_docs(self) -> int:
        return sum(len(c) for c in self._buf)

    @property
    def total_docs(self) -> int:
        """Total assigned doc ids (segments + buffer, deleted included)."""
        return sum(s.num_docs for s in self._segments) + self.buffered_docs

    @property
    def num_segments(self) -> int:
        return len(self._segments)

    def _next_name(self) -> str:
        name = f"seg{self._seg_counter}"
        self._seg_counter += 1
        return name

    # -- mutation ----------------------------------------------------------

    def add(self, vectors, metadata=None) -> np.ndarray:
        """Buffer rows; returns their assigned global doc ids.  Buffered
        rows become searchable at the next flush/refresh/commit.

        ``metadata``: per-row structured fields for filtered search — a
        ``{field: (n,) ints}`` mapping or a prebuilt
        :class:`repro.core.types.DocMetadata` with one row per added
        vector.  All adds into one flush (and, via merges, one index) must
        agree on the field set; rows ride into the built segment's
        ``AnnIndex.metadata`` and survive flush/merge/commit."""
        rows = np.asarray(vectors, np.float32)
        if rows.ndim == 1:
            rows = rows[None, :]
        if rows.ndim != 2 or rows.shape[0] == 0:
            raise ValueError(f"add expects (n, dim) rows, got {rows.shape}")
        md = builder.build_metadata(metadata, rows.shape[0])
        if md is not None and self.mesh is not None:
            raise ValueError("metadata is one-device only (shards=1)")
        start = self.total_docs
        self._buf.append(rows)
        self._buf_live.append(np.ones(rows.shape[0], bool))
        self._buf_md.append(md)
        if (
            self.max_buffered_docs is not None
            and self.buffered_docs >= self.max_buffered_docs
        ):
            self.flush()
        return np.arange(start, start + rows.shape[0], dtype=np.int64)

    def delete(self, ids) -> int:
        """Flip liveDocs bits for the given global doc ids (buffered rows
        included).  Returns the number of newly deleted docs; deleting a
        dead id is a no-op, an unknown id raises."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        bases = np.cumsum([0] + [s.num_docs for s in self._segments])
        flushed_total = int(bases[-1])
        newly = 0
        for gid in ids:
            gid = int(gid)
            if gid < 0 or gid >= self.total_docs:
                raise IndexError(
                    f"unknown doc id {gid} (have {self.total_docs} docs)"
                )
            if gid < flushed_total:
                si = int(np.searchsorted(bases, gid, side="right")) - 1
                seg, loc = self._segments[si], gid - int(bases[si])
                if seg.live[loc]:
                    seg.live[loc] = False
                    newly += 1
                    self._changed = True
            else:
                off = gid - flushed_total
                for chunk in self._buf_live:
                    if off < len(chunk):
                        if chunk[off]:
                            chunk[off] = False
                            newly += 1
                        break
                    off -= len(chunk)
        return newly

    def flush(self) -> bool:
        """Build buffered rows into a fresh immutable segment through the
        method's BuildPipeline, then let the merge policy react.  Returns
        True when a segment was written."""
        if not self._buf:
            return False
        n = self.buffered_docs
        with obs.span("writer.flush", rows=n, segments=len(self._segments),
                      shards=self.shards, chip_rows=-(-n // self.shards)):
            rows = np.concatenate(self._buf, axis=0)
            live = np.concatenate(self._buf_live, axis=0)
            if self.mesh is not None:
                self._segments.append(self._build_sharded_segment(
                    rows, live, normalized=False))
            else:
                md = _concat_metadata(self._buf_md)
                ann = self._build_segment(
                    jnp.asarray(rows), normalized=False, metadata=md
                )
                self._segments.append(
                    Segment(
                        ann=ann, live=live, name=self._next_name(),
                        source=self._source_sidecar(ann, rows, normalized=False),
                    )
                )
            self._buf, self._buf_live, self._buf_md = [], [], []
            self._changed = True
            self.maybe_merge()
        return True

    def _build_segment(
        self, rows: jax.Array, normalized: bool, metadata=None
    ) -> AnnIndex:
        return AnnIndex.build(
            rows, self.config,
            rerank_store=self.rerank_store, use_kernel=self.use_kernel,
            primary_postings=self.primary_postings,
            postings_group=self.postings_group,
            normalized=normalized,
            metadata=metadata,
        )

    def _build_sharded_segment(
        self, rows: np.ndarray, live: np.ndarray, normalized: bool
    ) -> Segment:
        """One segment spread over the mesh: contiguous parts of ``rows``
        (sizes differ by at most one) padded with zero rows to one block per
        chip, each part put from the host onto its own chip, then built
        there by the sharded BuildPipeline stages."""
        shards = self.mesh.size
        parts = np.array_split(rows, shards)
        block = max(1, len(parts[0]))
        if len(rows) != shards * block:
            padded = np.zeros((shards * block, rows.shape[1]), np.float32)
            for s, part in enumerate(parts):
                padded[s * block : s * block + len(part)] = part
            rows = padded
        axes = tuple(self.mesh.axis_names)
        v = jax.device_put(rows, NamedSharding(self.mesh, P(axes, None)))
        bp = builder.make_build_pipeline(
            self.config, self.rerank_store, self.primary_postings,
            self.postings_group,
        )
        idx = bp.sharded_build_fn(
            self.mesh, axes, n_total=len(live), normalized=normalized
        )(v)
        ann = AnnIndex(config=self.config, index=idx, use_kernel=self.use_kernel)
        seg = Segment(
            ann=ann, live=live, name=self._next_name(),
            shard_rows=tuple(len(p) for p in parts),
        )
        if idx.vectors is None:
            # The merge operand when the store dropped the originals:
            # normalized on the chips, as the build did, then fetched.
            vn = v if normalized else bruteforce.l2_normalize(v)
            seg.source = np.asarray(vn, np.float32)[seg.stored_rows()]
        return seg

    @staticmethod
    def _source_sidecar(
        ann: AnnIndex, rows: np.ndarray, normalized: bool
    ) -> Optional[np.ndarray]:
        """Host-side normalized originals when the built index dropped them
        (the exact rows a rerank_store='exact' build would have stored, so
        merge results stay bitwise independent of the store choice)."""
        if ann.index.vectors is not None:
            return None
        if not normalized:
            rows = np.asarray(bruteforce.l2_normalize(jnp.asarray(rows)))
        return np.asarray(rows, np.float32)

    # -- merging -----------------------------------------------------------

    def maybe_merge(self) -> int:
        """Run the merge policy to a fixed point; returns merges done."""
        if self.merge_policy is None:
            return 0
        done = 0
        while True:
            rng = self.merge_policy.find_merge(self._segments)
            if rng is None:
                return done
            self._merge_range(*rng)
            done += 1

    def force_merge(self, max_segments: int = 1) -> None:
        """Compact to at most ``max_segments`` segments and expunge every
        delete (a full merge with ``max_segments=1`` leaves one fully-live
        segment identical to a monolithic build of the live corpus)."""
        self.flush()
        if max_segments < 1:
            raise ValueError("max_segments must be >= 1")
        while len(self._segments) > max_segments:
            # Cheapest adjacent pair first (Lucene's smallest-merge bias).
            sizes = [s.num_live for s in self._segments]
            i = min(
                range(len(sizes) - 1), key=lambda j: sizes[j] + sizes[j + 1]
            )
            self._merge_range(i, i + 2)
        for i in range(len(self._segments) - 1, -1, -1):
            if self._segments[i].del_count:
                self._merge_range(i, i + 1)

    def _merge_range(self, start: int, end: int) -> None:
        """Rebuild segments [start, end) as one: concatenate their live
        normalized originals (add order preserved) and run the same
        BuildPipeline with ``normalized=True`` — deleted rows drop out and
        ids after the range remap, exactly like a Lucene merge."""
        group = self._segments[start:end]
        for s in group:
            if s.source_rows() is None:
                raise ValueError(
                    "merging " + _NEEDS_VECTORS_MSG
                    + f" or a source sidecar; segment {s.name!r} has neither"
                )
        rows = np.concatenate(
            [s.source_rows()[s.live] for s in group], axis=0
        )
        if rows.shape[0] == 0:
            # Every row dead: drop the segments outright.
            del self._segments[start:end]
            self._changed = True
            return
        if self.mesh is not None:
            self._segments[start:end] = [self._build_sharded_segment(
                rows, np.ones(rows.shape[0], bool), normalized=True)]
            self._changed = True
            return
        md = _concat_metadata(
            [s.ann.metadata for s in group], rows_kept=[s.live for s in group]
        )
        ann = self._build_segment(jnp.asarray(rows), normalized=True, metadata=md)
        merged = Segment(
            ann=ann, live=np.ones(rows.shape[0], bool),
            name=self._next_name(),
            source=self._source_sidecar(ann, rows, normalized=True),
        )
        self._segments[start:end] = [merged]
        self._changed = True

    # -- visibility --------------------------------------------------------

    def refresh(self) -> SegmentedAnnIndex:
        """Near-real-time reader (Lucene openIfChanged): flush the buffer
        and return a point-in-time snapshot.  The epoch advances IFF
        something changed; an unchanged refresh returns the cached reader,
        so epoch-keyed serving caches stay warm."""
        with obs.span("writer.refresh", rows=self.total_docs,
                      segments=len(self._segments)):
            self.flush()
            if self._reader is None or self._changed:
                old = self._reader
                self._reader = SegmentedAnnIndex(
                    self.config,
                    [s.snapshot() for s in self._segments],
                    use_kernel=self.use_kernel,
                    global_stats=self.global_stats,
                    mesh=self.mesh,
                )
                if (old is not None and self.mesh is None
                        and packed_mod.stats_static(self.config)):
                    # Hand the old snapshot's packed buffers to the new reader:
                    # an append-only refresh absorbs them via a donated
                    # incremental repack (core/packed.py).  The old reader
                    # lazily repacks if searched again after donation.  Other
                    # encodings repack fully, so the old buffers stay with the
                    # old reader and are freed with it, before the new pack.
                    self._reader._packed_prior = old._packed
                    old._packed = None
                self._changed = False
            return self._reader

    def commit(self, path: Optional[str] = None) -> int:
        """Flush + durably persist a generation-numbered commit point.

        Layout: one v1 index dir per segment (written once — segments are
        immutable, so later commits reuse them), a per-generation live file
        per segment carrying deletes, and ``segments_{gen}.json`` written
        LAST via write-to-temp + ``os.replace`` — a reader either sees the
        complete new generation or the previous one, never a torn commit.
        Superseded segment dirs / live files are left for older generations
        (no GC, like Lucene without a deletion policy)."""
        path = path if path is not None else self.path
        if path is None:
            raise ValueError("commit needs a path (or IndexWriter(path=...))")
        if self.mesh is not None:
            raise ValueError("commit points are one-device only (shards=1)")
        self.path = path
        self.flush()
        os.makedirs(path, exist_ok=True)
        commits = find_commits(path)
        on_disk = commits[-1][0] if commits else 0
        if on_disk != self._last_gen:
            # Lineage guard (Lucene's write.lock analog): this directory
            # holds commits this writer never read — committing would reuse
            # segment names against another writer's dirs and silently
            # corrupt the new generation.
            raise ValueError(
                f"{path!r} holds commit generation {on_disk}, but this "
                f"writer last saw generation {self._last_gen}; open the "
                "directory with IndexWriter.open(path) (or commit to a "
                "fresh directory) instead of committing over a foreign "
                "commit history"
            )
        gen = on_disk + 1
        entries = []
        for seg in self._segments:
            seg_dir = os.path.join(path, seg.name)
            if not os.path.exists(os.path.join(seg_dir, "config.json")):
                seg.ann.save(seg_dir)
            if seg.source is not None:
                src_file = os.path.join(seg_dir, "source.npz")
                if not os.path.exists(src_file):
                    np.savez_compressed(src_file, source=seg.source)
            entry = {
                "name": seg.name,
                "num_docs": seg.num_docs,
                "del_count": seg.del_count,
                "live_file": None,
            }
            if seg.del_count:
                live_file = os.path.join(seg.name, f"live_gen{gen}.npz")
                np.savez_compressed(
                    os.path.join(path, live_file), live=seg.live
                )
                entry["live_file"] = live_file
            entries.append(entry)
        meta = {
            "format_version": SEGMENTS_FORMAT_VERSION,
            "generation": gen,
            "method": _METHOD_BY_CONFIG[type(self.config)],
            "config": index_mod._config_to_json(self.config),
            "total_docs": sum(s.num_docs for s in self._segments),
            "num_live": sum(s.num_live for s in self._segments),
            "segments": entries,
            "use_kernel": self.use_kernel,
            "global_stats": self.global_stats,
        }
        final = os.path.join(path, f"segments_{gen}.json")
        tmp = final + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=2)
        os.replace(tmp, final)
        self._last_gen = gen
        return gen
