"""Staged retrieval pipeline: one search architecture for every encoding.

The paper's three Lucene encodings (fake words, lexical LSH, k-d trees) and
the brute-force oracle all share one logical flow:

    encode query  ->  match candidates  ->  [optional blockmax prune]
                  ->  optional exact rerank

This module makes that flow structural.  A :class:`SearchPipeline` composes
three pluggable stages, each a frozen (hashable, jit-static) dataclass:

  * **QueryEncoder** — ``encoder(index, q_norm) -> q_rep``: the method's
    query representation (tf row / MinHash signature / reduced point /
    identity for brute force).  Takes the index so reductions fitted at
    build time (k-d tree PCA) travel with the index pytree.
  * **Matcher** — ``matcher(index, q_rep, depth, *, bm=None, use_kernel=None)
    -> (scores (B, d), ids (B, d))``: the approximate match phase.  Every
    matcher has two realizations selected by ``use_kernel`` (default: the
    fused streaming score->top-k Pallas kernel on TPU, the XLA reference
    elsewhere — docs/DESIGN.md §4).  :class:`BlockMaxMatcher` is the pruning
    stage: it consumes a ``BlockMaxIndex`` (``bm``) and routes the kept
    blocks through the fused gathered kernel (docs/DESIGN.md §6).
  * **Reranker** — ``reranker(index, queries, cand_ids, k)``: exact cosine
    over the stored original vectors (the refinement the paper describes).

Because stages take the index pytree as an explicit argument, the *same*
stage objects run single-device under ``jit`` and per-shard under
``shard_map`` (core/distributed.py), and a new encoding is a ~50-line
encoder+matcher pair, not a new module.  ``repro.core.index.AnnIndex`` builds
and owns a pipeline; the per-method ``search()`` functions are thin wrappers
over these stages.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import bruteforce
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    GraphConfig,
    KdTreeConfig,
    LexicalLshConfig,
    SearchParams,
)

AnyConfig = Union[
    FakeWordsConfig, LexicalLshConfig, KdTreeConfig, BruteForceConfig,
    GraphConfig,
]


# --------------------------------------------------------------------------
# Query encoders
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TfRowEncoder:
    """Fake-words: sign-split quantized term-frequency row (B, 2m) int32."""

    config: FakeWordsConfig

    def __call__(self, index, q_norm: jax.Array) -> jax.Array:
        from repro.core import fakewords

        return fakewords.encode_queries(q_norm, self.config, normalized=True)


@dataclasses.dataclass(frozen=True)
class MinHashEncoder:
    """Lexical LSH: MinHash signature (B, h*b) uint32."""

    config: LexicalLshConfig

    def __call__(self, index, q_norm: jax.Array) -> jax.Array:
        from repro.core import lexical_lsh

        return lexical_lsh.encode(q_norm, self.config)


@dataclasses.dataclass(frozen=True)
class ReducedPointEncoder:
    """k-d tree: project through the reduction fitted at build time."""

    def __call__(self, index, q_norm: jax.Array) -> jax.Array:
        from repro.core import kdtree

        return kdtree.reduce_queries(index, q_norm, normalized=True)


@dataclasses.dataclass(frozen=True)
class IdentityEncoder:
    """Brute force: the unit-normalized query itself."""

    def __call__(self, index, q_norm: jax.Array) -> jax.Array:
        return q_norm


# --------------------------------------------------------------------------
# Matchers
# --------------------------------------------------------------------------


def _use_kernel(use_kernel: Optional[bool]) -> bool:
    from repro.kernels.fused_topk import ops as fused

    return fused.resolve_use_kernel(use_kernel)


def lookup_filt_bits(mask: jax.Array, ids: jax.Array) -> jax.Array:
    """Per-candidate keep bits of a per-doc bitmap ((N,) shared or (B, N)
    per-query) at candidate id positions; id -1 slots read doc 0 (callers
    AND with ``ids >= 0``)."""
    safe = jnp.maximum(ids, 0)
    bits = mask[safe] if mask.ndim == 1 else jnp.take_along_axis(mask, safe, axis=1)
    return bits != 0


def mask_and_topk(
    s: jax.Array, i: jax.Array, keep: jax.Array, depth: int, n: int
) -> Tuple[jax.Array, jax.Array]:
    """THE shared mask-then-re-reduce tail of every post-hoc candidate
    filter (deletes AND predicate bitmaps): kept slots retain the inner
    stage's (score, id); dropped slots become (-inf, -1); the survivors
    re-reduce to the top ``min(depth, n)``.  Equal-score ties keep the
    inner stage's lowest-doc-id order (``lax.top_k`` is stable)."""
    s = jnp.where(keep, s, -jnp.inf)
    i = jnp.where(keep, i, -1)
    d_out = min(depth, n)
    top_s, pos = jax.lax.top_k(s, d_out)
    return top_s, jnp.take_along_axis(i, pos, axis=-1)


def _dense_filtered_topk(
    scores: jax.Array, depth: int, filt: Optional[jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """Dense-matrix XLA top-k with the kernel's filter contract: masked
    slots take (-inf, -1).  ``filt=None`` is exactly ``jax.lax.top_k``."""
    from repro.kernels.fused_topk import ref as fused_ref

    if filt is None:
        return jax.lax.top_k(scores, depth)
    s, i = jax.lax.top_k(fused_ref.apply_filt(scores, filt), depth)
    return s, jnp.where(s == -jnp.inf, -1, i)


def _streaming_topk_tiled(
    score_tile_fn, n_local: int, batch: int, depth: int, tile: int,
    unroll: bool = False, filt: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming top-d over document tiles with a running merge: the
    (B, n_local) score matrix never materializes in HBM (§Perf C2).  The XLA
    realization of the fused kernel's memory behavior, used for shards too
    large for a dense GEMM when the Pallas kernel is off.

    score_tile_fn(start) -> (B, tile) scores for docs [start, start+tile).
    Ties break on the lowest doc id: earlier tiles enter the merge first and
    ``lax.top_k`` prefers the earlier position on equal scores.
    """
    n_tiles = -(-n_local // tile)
    d = min(depth, tile)
    init_s = jnp.full((batch, depth), -jnp.inf, jnp.float32)
    init_i = jnp.full((batch, depth), -1, jnp.int32)
    if filt is not None:
        f_full = filt if filt.ndim == 2 else filt[None, :]
        pad = n_tiles * tile - f_full.shape[1]
        if pad:  # pre-pad so per-tile slices never clamp
            f_full = jnp.concatenate(
                [f_full, jnp.zeros((f_full.shape[0], pad), f_full.dtype)],
                axis=1,
            )

    def body(carry, t_idx):
        best_s, best_i = carry
        start = t_idx * tile
        s = score_tile_fn(start).astype(jnp.float32)  # (B, tile)
        ids = start + jnp.arange(tile, dtype=jnp.int32)[None, :]
        valid = ids < n_local
        if filt is not None:
            f_tile = jax.lax.dynamic_slice_in_dim(f_full, start, tile, axis=1)
            valid = valid & (f_tile != 0)
        s = jnp.where(valid, s, -jnp.inf)
        loc_s, pos = jax.lax.top_k(s, d)
        loc_i = jnp.take_along_axis(jnp.broadcast_to(ids, s.shape), pos, axis=-1)
        if filt is not None:
            loc_i = jnp.where(loc_s == -jnp.inf, -1, loc_i)
        all_s = jnp.concatenate([best_s, loc_s], axis=-1)
        all_i = jnp.concatenate([best_i, loc_i], axis=-1)
        top_s, top_pos = jax.lax.top_k(all_s, depth)
        return (top_s, jnp.take_along_axis(all_i, top_pos, axis=-1)), None

    (best_s, best_i), _ = jax.lax.scan(
        body, (init_s, init_i), jnp.arange(n_tiles, dtype=jnp.int32),
        unroll=unroll,  # analysis mode: HLO cost analysis counts a while
        #                 body once; roofline lowers the unrolled loop
    )
    return best_s, best_i


@dataclasses.dataclass(frozen=True)
class FakeWordsMatcher:
    """Classic (tf-idf) or dot (quantized integer) scoring over the stored
    term-frequency matrix; df-prune keep-mask folded into the query operand.

    ``score_tile`` (when set) bounds the XLA fallback's working set: shards
    larger than ``2 * score_tile`` docs stream tile-by-tile with a running
    top-d merge instead of materializing the dense (B, N) score matrix.

    ``df_num_docs`` (when set) is the collection size the df-prune keep-mask
    thresholds against instead of the index's own row count — the segmented
    index (docs/DESIGN.md §11) scores every segment with GLOBAL collection
    statistics, Lucene-IndexSearcher style.
    """

    scoring: str = "classic"
    df_max_ratio: float = 1.0
    signed_store: bool = False
    score_tile: Optional[int] = None
    tile_unroll: bool = False
    df_num_docs: Optional[int] = None

    def operands(self, index, q_tf: jax.Array, dtype) -> Tuple[jax.Array, jax.Array]:
        """(query operand, stored matrix) for this scoring mode; ``dtype``
        is the dot-mode query dtype (int8 for the MXU kernel, int32 for the
        XLA einsum).  The query is widened to the stored matrix, which a
        packed view keeps lane-aligned."""
        from repro.core import fakewords
        from repro.kernels.fused_topk import ops as fused

        n = self.df_num_docs if self.df_num_docs is not None else index.num_docs
        if self.scoring == "classic":
            qv = fakewords.classic_query(
                index, q_tf, self.df_max_ratio, num_docs=n)
            docs = index.scored
        elif self.signed_store:
            # index.tf holds the SIGNED (N, m) matrix; fold the sign-split
            # keep mask down to m terms.
            keep = fakewords.df_prune_mask(index.df, n, self.df_max_ratio)
            m = index.tf.shape[1]
            keep_m = keep[:m] & keep[m:] if keep.shape[0] == 2 * m else keep[:m]
            qv = (fakewords.signed_query(q_tf) * keep_m).astype(dtype)
            docs = index.tf
        else:
            qv = fakewords.dot_query(
                index, q_tf, self.df_max_ratio, dtype=dtype, num_docs=n)
            docs = index.tf
        return fused.pad_query(qv, docs.shape[-1]), docs

    def quantized_query(self, index, q_tf: jax.Array) -> jax.Array:
        """bf16 query operand for the packed-postings path (docs/DESIGN.md
        §12): both scoring modes dequantize the store to the query dtype in
        the score stage, so the query itself must be float."""
        from repro.core import fakewords

        n = self.df_num_docs if self.df_num_docs is not None else index.num_docs
        if self.scoring == "classic":
            return fakewords.classic_query(
                index, q_tf, self.df_max_ratio, num_docs=n)
        if index.pq.cols * 2 == index.df.shape[0]:
            # Genuinely signed packed store (N, m); the pipeline-built
            # signed_store index still stores the sign-split 2m columns.
            keep = fakewords.df_prune_mask(index.df, n, self.df_max_ratio)
            m = index.pq.cols
            keep_m = keep[:m] & keep[m:]
            return (fakewords.signed_query(q_tf) * keep_m).astype(jnp.bfloat16)
        return fakewords.dot_query(
            index, q_tf, self.df_max_ratio, dtype=jnp.bfloat16, num_docs=n)

    def _dense_scores(self, qv: jax.Array, docs: jax.Array) -> jax.Array:
        if self.scoring == "classic":
            return jnp.einsum(
                "bt,nt->bn", qv, docs, preferred_element_type=jnp.float32
            )
        return jnp.einsum(
            "bt,nt->bn", qv, docs.astype(jnp.int32),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)

    def __call__(
        self, index, q_tf: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.kernels.fused_topk import ops as fused

        # n_docs: logical row count when the stored matrix carries tail
        # padding (core/packed.py bucket ladder); None = every stored row.
        nd = index.num_docs if n_docs is None else n_docs
        ndk = None if nd == index.num_docs else nd
        d = min(depth, nd)
        if index.pq is not None:
            from repro.kernels.fused_topk import ref as fused_ref

            qv = self.quantized_query(index, q_tf)
            pq = index.pq
            if _use_kernel(use_kernel):
                return fused.postings_topk(pq, qv, d, filt=filt, n_docs=ndk)
            if self.score_tile is not None and index.num_docs > 2 * self.score_tile:
                return fused_ref.streaming_topk_quantized_ref(
                    qv, pq.q, pq.scale, d, pq.bits, pq.group,
                    tile=self.score_tile, filt=filt, n_docs=ndk,
                )
            return fused_ref.quantized_topk_ref(
                qv, pq.q, pq.scale, d, pq.bits, pq.group, filt=filt,
                n_docs=ndk)
        if _use_kernel(use_kernel):
            qv, docs = self.operands(index, q_tf, dtype=jnp.int8)
            return fused.fused_topk(qv, docs, d, filt=filt, n_docs=ndk)
        qv, docs = self.operands(index, q_tf, dtype=jnp.int32)
        if self.score_tile is not None and index.num_docs > 2 * self.score_tile:
            def tile_scores(start):
                rows = jax.lax.dynamic_slice_in_dim(
                    docs, start, self.score_tile, axis=0)
                return self._dense_scores(qv, rows)

            return _streaming_topk_tiled(
                tile_scores, nd, q_tf.shape[0], d,
                self.score_tile, unroll=self.tile_unroll, filt=filt,
            )
        scores = self._dense_scores(qv, docs)
        if ndk is not None:
            scores = scores[:, :nd]
            filt = None if filt is None else filt[..., :nd]
        return _dense_filtered_topk(scores, d, filt)


@dataclasses.dataclass(frozen=True)
class LshMatcher:
    """MinHash signature-collision counting (integer compare+reduce)."""

    def __call__(
        self, index, sig_q: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.core import lexical_lsh
        from repro.kernels.fused_topk import ops as fused

        nd = index.num_docs if n_docs is None else n_docs
        ndk = None if nd == index.num_docs else nd
        d = min(depth, nd)
        sig_q = fused.pad_query(sig_q, index.sig.shape[-1])
        if _use_kernel(use_kernel):
            return fused.lsh_topk(sig_q, index.sig, d, filt=filt, n_docs=ndk)
        scores = lexical_lsh.match_scores(sig_q, index.sig).astype(jnp.float32)
        if ndk is not None:
            scores = scores[:, :nd]
            filt = None if filt is None else filt[..., :nd]
        return _dense_filtered_topk(scores, d, filt)


@dataclasses.dataclass(frozen=True)
class KdScanMatcher:
    """Exact L2 NN in the reduced space as a streaming matmul (the
    TPU-idiomatic equivalent of the paper's BKD tree; kdtree.py §b)."""

    def __call__(
        self, index, q_reduced: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.kernels.fused_topk import ops as fused

        nd = index.num_docs if n_docs is None else n_docs
        ndk = None if nd == index.num_docs else nd
        d = min(depth, nd)
        if _use_kernel(use_kernel):
            lifted = (
                index.lifted if index.lifted is not None
                else fused.lift_l2(index.reduced)
            )
            return fused.scan_l2_topk(
                lifted, q_reduced, d, filt=filt, n_docs=ndk)
        d_norm2 = jnp.sum(index.reduced**2, axis=-1)  # (N,)
        dots = q_reduced @ index.reduced.T  # (B, N)
        neg_d2 = 2.0 * dots - d_norm2[None, :]
        if ndk is not None:
            neg_d2 = neg_d2[:, :nd]
            filt = None if filt is None else filt[..., :nd]
        return _dense_filtered_topk(neg_d2, d, filt)


@dataclasses.dataclass(frozen=True)
class KdTreeMatcher:
    """Faithful batched k-d tree DFS (the paper's data structure; documented
    TPU-hostile, kept for fidelity).  Ignores ``use_kernel``."""

    def __call__(
        self, index, q_reduced: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,  # unused: host DFS has no padded rows
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.core import kdtree

        n = index.num_docs
        s, i = kdtree.tree_search(index, q_reduced, min(depth, n))
        if filt is None:
            return s, i
        # The host DFS cannot thread a bitmap through its visit order; mask
        # its depth candidates post-hoc (best-effort, like a post-filter —
        # use the scan backend for exact filtered kd search).
        keep = (i >= 0) & lookup_filt_bits(filt, i)
        return mask_and_topk(s, i, keep, min(depth, n), n)


@dataclasses.dataclass(frozen=True)
class CosineMatcher:
    """Exact cosine over the stored unit vectors (brute-force oracle)."""

    def __call__(
        self, index, q_norm: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.kernels.fused_topk import ops as fused

        nd = index.num_docs if n_docs is None else n_docs
        ndk = None if nd == index.num_docs else nd
        d = min(depth, nd)
        if index.pq is not None:
            from repro.kernels.fused_topk import ref as fused_ref

            if _use_kernel(use_kernel):
                return fused.postings_topk(
                    index.pq, q_norm, d, filt=filt, n_docs=ndk)
            return fused_ref.quantized_topk_ref(
                q_norm, index.pq.q, index.pq.scale, d,
                index.pq.bits, index.pq.group, filt=filt, n_docs=ndk,
            )
        q_norm = fused.pad_query(q_norm, index.vectors.shape[-1])
        if _use_kernel(use_kernel):
            return fused.cosine_topk(
                index.vectors, q_norm, d, filt=filt, n_docs=ndk)
        scores = q_norm @ index.vectors.T  # (B, N)
        if ndk is not None:
            scores = scores[:, :nd]
            filt = None if filt is None else filt[..., :nd]
        return _dense_filtered_topk(scores, d, filt)


@dataclasses.dataclass(frozen=True)
class GraphMatcher:
    """Batched beam search over the flat proximity graph (docs/DESIGN.md
    §15) — the repo's first sublinear match stage: per-query work is
    ~``iters * beam * total_degree`` scored rows, independent of N.

    ``ef`` / ``beam`` / ``iters`` are static fields (the matcher is a
    jit-static argument), so the traversal compiles to one fixed-iteration
    ``fori_loop`` executable per query-batch shape.  ``filt`` (liveDocs ∧
    predicate) is consulted INSIDE traversal: masked nodes stay traversable
    (connectivity survives low selectivity) but are never emitted.
    """

    ef: int = 64
    beam: int = 4
    iters: int = 32

    def __call__(
        self, index, q_norm: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.core import graph

        assert bm is None, "graph search has no blockmax stage"
        nd = index.num_docs if n_docs is None else n_docs
        d = min(depth, nd)
        return graph.search_graph(
            index.vectors, index.neighbors, index.entry, q_norm, d,
            ef=self.ef, beam=self.beam, iters=self.iters, n_docs=nd,
            use_kernel=_use_kernel(use_kernel), filt=filt,
        )


@dataclasses.dataclass(frozen=True)
class BlockMaxMatcher:
    """Two-stage blockmax pruning (docs/DESIGN.md §6) as a matcher stage:
    optimistic block-bound pass -> keep ``n_keep`` blocks -> exact scoring of
    the gathered rows through the fused gathered streaming top-k kernel.
    Mode (classic / dot-int8 / LSH presence bitmaps) travels with ``bm``."""

    n_keep: int

    def __call__(
        self, index, q_rep: jax.Array, depth: int,
        bm=None, use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
        n_docs: Optional[int] = None,  # padded rows ride the filt bitmap
    ) -> Tuple[jax.Array, jax.Array]:
        from repro.core import blockmax

        assert bm is not None, "BlockMaxMatcher needs a BlockMaxIndex (bm=)"
        return blockmax.pruned_topk(
            index, bm, q_rep, self.n_keep, depth, use_kernel=use_kernel,
            filt=filt,
        )


@dataclasses.dataclass(frozen=True)
class FilterMask:
    """Per-doc predicate masking as a match-stage wrapper — Lucene liveDocs
    generalized to arbitrary bitmaps (docs/DESIGN.md §11, §13).

    Masked docs come back as ``(-inf, -1)`` INSIDE the match stage — never
    post-filtered from its output — so ``depth`` semantics survive.  Two
    realizations, selected per call:

      * ``native=True`` — the bitmap threads straight into the inner
        matcher's score stage (the kernels' ``filt`` operand / the XLA
        refs' pre-top-k mask): ONE kernel pass, exact at any selectivity.
        This is the predicate-filter path.
      * ``native=False`` (default) — depth inflation: ask the inner matcher
        for ``depth + extra`` candidates (``extra`` is a bucketed upper
        bound on the masked-out count, so at least ``depth`` kept
        candidates are present whenever that many exist) and re-reduce to
        the top ``depth`` kept docs via :func:`mask_and_topk`.  This is the
        historical liveDocs/deletes path, kept because the delete stream
        mutates the mask without re-specializing the inner match.

    Equal-score ties keep the inner matcher's lowest-doc-id order
    (``lax.top_k`` is stable), so a segment with deletes returns exactly
    what a segment never containing the dead rows would.

    ``mask`` is an explicit ``(N,)`` (or per-query ``(B, N)``) bool/int
    operand (nonzero = keep) rather than an index leaf: the segment index
    stays immutable while its mask mutates, exactly like Lucene's sidecar
    ``.liv`` bitsets.  ``extra`` is bucketed (next power of two) by the
    caller so a delete stream does not recompile per delete.
    """

    inner: Any
    extra: int = 0

    def __call__(
        self, index, q_rep: jax.Array, depth: int, mask: jax.Array,
        bm=None, use_kernel: Optional[bool] = None, native: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        n = index.num_docs
        if native:
            return self.inner(
                index, q_rep, min(depth, n), bm=bm, use_kernel=use_kernel,
                filt=mask,
            )
        d_in = min(depth + self.extra, n)
        s, i = self.inner(index, q_rep, d_in, bm=bm, use_kernel=use_kernel)
        keep = (i >= 0) & lookup_filt_bits(mask, i)
        return mask_and_topk(s, i, keep, depth, n)


# Backwards-compatible name for the deletes-only wrapper this generalizes.
LiveDocsMatcher = FilterMask


# --------------------------------------------------------------------------
# Rerankers
# --------------------------------------------------------------------------


def candidate_scores(
    index, queries: jax.Array, cand_ids: jax.Array, quantized: bool = False
) -> jax.Array:
    """(B, d) cosine of each candidate against its query; id -1 = padding,
    masked to -inf.  The ONE rerank-gather both rerankers, the packed
    search and the distributed local-rerank merge share.  ``quantized``
    reads the int8 :class:`repro.core.types.QuantizedStore` (``index.vq``)
    — the gather moves ~4x fewer HBM bytes and dequantizes with one
    per-doc multiply — instead of the fp32 originals."""
    safe = jnp.maximum(cand_ids, 0)
    if quantized:
        assert index.vq is not None, (
            "quantized rerank requires the index to carry an int8 store "
            "(build with rerank_store='int8')"
        )
        cand = index.vq.q[safe]  # (B, d, dim) int8 gather
        s = jnp.einsum("bd,bcd->bc", queries, cand.astype(jnp.float32))
        s = s * index.vq.scale[safe]
    else:
        assert index.vectors is not None, (
            "rerank requires the index to keep original vectors "
            "(build with keep_vectors=True / rerank_store='exact')"
        )
        # A packed view stores lane-aligned rows: slice the gathered
        # candidates back to the query's width, never the corpus.
        cand = index.vectors[safe][..., : queries.shape[-1]]
        s = jnp.einsum("bd,bcd->bc", queries, cand)
    return jnp.where(cand_ids >= 0, s, -jnp.inf)


@dataclasses.dataclass(frozen=True)
class ExactCosineReranker:
    """Gather the depth-d candidates' original vectors, exact cosine, top-k
    (id -1 = padding, masked to -inf)."""

    def __call__(
        self, index, queries: jax.Array, cand_ids: jax.Array, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        assert index.vectors is not None, (
            "rerank requires the index to keep original vectors "
            "(build with keep_vectors=True)"
        )
        return bruteforce.rerank_exact(
            index.vectors, queries, cand_ids, k, normalized=True
        )


@dataclasses.dataclass(frozen=True)
class QuantizedCosineReranker:
    """Rerank from the int8 + per-doc-scale store (docs/DESIGN.md §8): same
    tie semantics as :class:`ExactCosineReranker`, score error bounded by
    ``||q||_1 * scale/2`` per candidate, ~4x fewer gather bytes."""

    def __call__(
        self, index, queries: jax.Array, cand_ids: jax.Array, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        scores = candidate_scores(index, queries, cand_ids, quantized=True)
        top_s, pos = jax.lax.top_k(scores, k)
        return top_s, jnp.take_along_axis(cand_ids, pos, axis=-1)


def default_reranker(index):
    """Exact rerank when fp32 originals are stored, else the int8 store."""
    if getattr(index, "vectors", None) is None and index.vq is not None:
        return QuantizedCosineReranker()
    return ExactCosineReranker()


# --------------------------------------------------------------------------
# The pipeline
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SearchPipeline:
    """encode -> match [-> blockmax prune] -> optional exact rerank.

    Frozen and hashable: a pipeline is a jit-static description of *how* to
    search; all array state stays in the index pytree (and optional ``bm``)
    passed to every call — which is exactly what lets the same pipeline run
    per-shard under ``shard_map``.
    """

    encoder: Any
    matcher: Any
    reranker: Any = ExactCosineReranker()

    def encode(self, index, queries: jax.Array) -> jax.Array:
        """Unit-normalize + method-specific query representation."""
        return self.encoder(index, bruteforce.l2_normalize(jnp.asarray(queries)))

    def search(
        self,
        index,
        queries: jax.Array,
        params: SearchParams = SearchParams(),
        bm=None,
        use_kernel: Optional[bool] = None,
        filt: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """End-to-end staged search (jitted; pipeline and params static).
        ``filt`` is a per-doc predicate bitmap ((N,) or (B, N), nonzero =
        keep) applied INSIDE the match stage's score pass."""
        q_norm = bruteforce.l2_normalize(jnp.asarray(queries))
        return _pipeline_search(self, index, q_norm, params, bm, use_kernel, filt)


@functools.partial(
    jax.jit, static_argnames=("pipe", "params", "use_kernel")
)
def _pipeline_search(
    pipe: SearchPipeline,
    index,
    q_norm: jax.Array,
    params: SearchParams,
    bm=None,
    use_kernel: Optional[bool] = None,
    filt: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    q_rep = pipe.encoder(index, q_norm)
    matcher = pipe.matcher
    d_s, d_i = matcher(
        index, q_rep, params.depth, bm=bm, use_kernel=use_kernel, filt=filt
    )
    if not params.rerank:
        return d_s[:, : params.k], d_i[:, : params.k]
    return pipe.reranker(index, q_norm, d_i, params.k)


@functools.partial(
    jax.jit,
    static_argnames=("matcher", "k", "depth", "rerank", "use_kernel", "reranker"),
)
def match_rerank(
    matcher,
    index,
    q_rep: jax.Array,
    queries: Optional[jax.Array],
    k: int,
    depth: int,
    rerank: bool,
    bm=None,
    use_kernel: Optional[bool] = None,
    reranker=None,
    filt: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Match + optional exact rerank from an already-encoded query — the
    shared tail of every per-method ``search()`` wrapper (queries must be
    unit-normalized when reranking).  ``reranker`` defaults to the store
    the index carries (fp32 originals, else the int8 quantized store).
    ``filt`` masks inside the match stage (one pass); rerank only re-scores
    survivors, so filtered docs can never resurface."""
    d_s, d_i = matcher(index, q_rep, depth, bm=bm, use_kernel=use_kernel,
                       filt=filt)
    if not rerank:
        return d_s[:, :k], d_i[:, :k]
    assert queries is not None
    if reranker is None:
        reranker = default_reranker(index)
    return reranker(index, queries, d_i, k)


# --------------------------------------------------------------------------
# Builders: every method is a stage configuration
# --------------------------------------------------------------------------


def make_encoder(config: AnyConfig):
    if isinstance(config, FakeWordsConfig):
        return TfRowEncoder(config)
    if isinstance(config, LexicalLshConfig):
        return MinHashEncoder(config)
    if isinstance(config, KdTreeConfig):
        return ReducedPointEncoder()
    if isinstance(config, (BruteForceConfig, GraphConfig)):
        return IdentityEncoder()
    raise TypeError(f"unknown config {type(config)}")


def make_matcher(
    config: AnyConfig,
    score_tile: Optional[int] = None,
    tile_unroll: bool = False,
):
    """The dense match stage for a method config.  ``score_tile`` activates
    the tiled-streaming XLA fallback for huge (sharded) fake-words corpora."""
    if isinstance(config, FakeWordsConfig):
        return FakeWordsMatcher(
            scoring=config.scoring,
            df_max_ratio=config.df_max_ratio,
            signed_store=config.signed_store,
            score_tile=score_tile,
            tile_unroll=tile_unroll,
        )
    if isinstance(config, LexicalLshConfig):
        return LshMatcher()
    if isinstance(config, KdTreeConfig):
        return KdTreeMatcher() if config.backend == "tree" else KdScanMatcher()
    if isinstance(config, BruteForceConfig):
        return CosineMatcher()
    if isinstance(config, GraphConfig):
        return GraphMatcher(
            ef=config.ef, beam=config.beam, iters=config.search_iters)
    raise TypeError(f"unknown config {type(config)}")


def build_pipeline(
    config: AnyConfig,
    score_tile: Optional[int] = None,
    tile_unroll: bool = False,
) -> SearchPipeline:
    return SearchPipeline(
        encoder=make_encoder(config),
        matcher=make_matcher(config, score_tile, tile_unroll),
    )
