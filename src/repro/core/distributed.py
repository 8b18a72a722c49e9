"""Pod-scale sharded ANN search (docs/DESIGN.md §5).

Lucene/Elasticsearch scale by sharding the inverted index across nodes: every
query fans out, each shard returns its local top-d, and a coordinator merges.
We reproduce that architecture with ``shard_map`` over the full device mesh:

  1. the corpus (tf matrix / signatures / reduced points + original vectors)
     is sharded over the flattened mesh axes on the document dimension;
  2. each shard scores locally (one GEMM over its slice) and takes a local
     top-d;
  3. *global top-depth merge*: one all-gather of (score, global_id) pairs -
     d*(4+4) bytes per shard, negligible next to the index scan - and the
     global top-d by match score, ties to the lower global id, exactly the
     candidates a one-device match over the whole corpus selects;
  4. *owned exact rerank*: each shard recomputes exact cosine for the global
     candidates it holds from its local original vectors (no cross-shard
     vector movement), and one max over shards of the (B, d) rerank scores
     assembles them in the global candidate order for a replicated top-k.

So a sharded search returns what one device returns over the same rows: the
global top-``depth`` by match score, then the exact rerank
(:func:`merge_global_depth`).  Each shard keeping and reranking its own
top-``depth`` instead would rerank ``shards * depth`` candidates: a
different, larger candidate set.

The per-shard match phase runs the SAME stage objects as single-device
search (:mod:`repro.core.pipeline`): ``make_sharded_search`` builds the
method's matcher from its config and calls it on each shard's local index
slice, so every encoding — fake words, lexical LSH, k-d scan, brute force —
gets the fan-out/merge architecture from one code path.

Build is also distributed — for EVERY encoding (:func:`build_sharded`, the
pod entry of the staged ``core/builder.py`` BuildPipeline): fake-words and
LSH postings are row-parallel, document-frequency statistics ``psum`` so
idf matches a single-node build exactly, and the kd-tree reduction fits
from psum'd global moments so every shard holds the identical model while
its rows never leave the shard.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pca
from repro.core import pipeline as pl
from repro.core.blockmax import BlockMaxIndex
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    FakeWordsIndex,
    FlatIndex,
    GraphConfig,
    GraphIndex,
    KdTreeConfig,
    KdTreeIndex,
    LexicalLshConfig,
    LshIndex,
    QuantizedStore,
)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """A device mesh with Auto axis types.  ``jax.make_mesh`` defaults to
    Explicit axes, under which the doc-sharded build and search (eager
    ``shard_map`` calls, gathers on sharded leaves) would have to run
    inside ``jax.set_mesh``; Auto axes let them run as plain calls."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def flat_axis_index(axes: Sequence[str]) -> jax.Array:
    """Row-major linear index of this shard over multiple mesh axes."""
    idx = jnp.int32(0)
    for name in axes:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def flat_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    size = 1
    for name in axes:
        size *= mesh.shape[name]
    return size


# --------------------------------------------------------------------------
# Sharding specs (document dimension) for every index type
# --------------------------------------------------------------------------


def _replicated_tree(model):
    """P() for every leaf of a nested reduction-model pytree."""
    return jax.tree_util.tree_map(lambda _: P(), model)


def _pspec_tree(
    kind: str,
    axes: Sequence[str],
    scored: bool = False,
    vectors: bool = True,
    reduction_spec=None,
    lifted: bool = True,
    vq: bool = False,
    tf: bool = True,
    pq=None,
):
    """The one place the per-type doc-dimension spec trees are written;
    :func:`index_pspec` / :func:`config_pspec` just derive the presence
    flags (from an instance or a config) and delegate here.

    ``pq`` is the spec placed at the quantized-postings slot: an exact
    :class:`QuantizedPostings` spec (from an instance, static metadata
    matching) or a bare prefix ``P`` that shard_map broadcasts over the
    q/scale leaves (from a config, where the packed column counts are not
    yet known)."""
    axes = tuple(axes)
    doc = P(axes, None)
    vec = doc if vectors else None
    # int8 rerank store: rows doc-sharded, per-doc scales shard with them.
    vqs = QuantizedStore(q=doc, scale=P(axes)) if vq else None
    if kind == "fake-words":
        return FakeWordsIndex(
            tf=doc if tf else None, idf=P(), norm=P(axes), df=P(),
            scored=doc if scored else None, vectors=vec, vq=vqs, pq=pq,
        )
    if kind == "lexical-lsh":
        return LshIndex(sig=doc, vectors=vec, vq=vqs)
    if kind == "kd-tree":
        return KdTreeIndex(
            reduced=doc, reduction=reduction_spec,
            lifted=doc if lifted else None, vectors=vec, vq=vqs,
        )
    if kind == "bruteforce":
        return FlatIndex(vectors=vec, vq=vqs, pq=pq)
    if kind == "hnsw":
        # Adjacency rows shard with the docs they belong to (neighbor ids
        # stay GLOBAL); the entry points are replicated like idf/df.
        return GraphIndex(vectors=doc, neighbors=doc, entry=P(), vq=vqs)
    raise ValueError(f"unknown index kind {kind!r}")


_TREE_BACKEND_MSG = (
    "kd-tree 'tree' backend cannot shard on documents; use backend='scan' "
    "(identical results, docs/DESIGN.md §3)"
)


def index_pspec(index, axes: Sequence[str]):
    """Doc-dimension sharding spec tree matching an index's present leaves.
    Works for every index type the pipeline serves."""
    doc = P(tuple(axes), None)
    if isinstance(index, FakeWordsIndex):
        return _pspec_tree(
            "fake-words", axes,
            scored=index.scored is not None,
            vectors=index.vectors is not None,
            vq=index.vq is not None,
            tf=index.tf is not None,
            pq=(
                dataclasses.replace(index.pq, q=doc, scale=doc)
                if index.pq is not None else None
            ),
        )
    if isinstance(index, LshIndex):
        return _pspec_tree(
            "lexical-lsh", axes, vectors=index.vectors is not None,
            vq=index.vq is not None,
        )
    if isinstance(index, KdTreeIndex):
        if index.split_dim is not None:
            raise ValueError(_TREE_BACKEND_MSG)
        return _pspec_tree(
            "kd-tree", axes,
            vectors=index.vectors is not None,
            reduction_spec=_replicated_tree(index.reduction),
            lifted=index.lifted is not None,
            vq=index.vq is not None,
        )
    if isinstance(index, FlatIndex):
        return _pspec_tree(
            "bruteforce", axes,
            vectors=index.vectors is not None,
            vq=index.vq is not None,
            pq=(
                dataclasses.replace(index.pq, q=doc, scale=doc)
                if index.pq is not None else None
            ),
        )
    if isinstance(index, GraphIndex):
        return _pspec_tree("hnsw", axes, vq=index.vq is not None)
    raise TypeError(f"unknown index {type(index)}")


def config_pspec(
    config,
    axes: Sequence[str],
    keep_vectors: bool = True,
    quantized_store: bool = False,
    postings_bits: int = 0,
):
    """Spec tree from a method config (when no index instance is at hand —
    e.g. dryrun cells that eval_shape through the sharded search).
    ``quantized_store`` marks the int8 rerank store present (built with
    ``rerank_store='int8'``, in which case fp32 vectors are absent).
    ``postings_bits`` (0 | 8 | 4) marks the primary postings encoding
    (docs/DESIGN.md §12); the packed-postings spec is a bare prefix ``P``
    since the packed column counts depend on the data dims."""
    doc = P(tuple(axes), None)
    if isinstance(config, FakeWordsConfig):
        # dot-int8 stores quantized tf natively (no separate pq leaf);
        # classic quantizes `scored` away; dot-int4 packs tf away.
        quant = postings_bits > 0 and (
            config.scoring == "classic" or postings_bits == 4
        )
        return _pspec_tree(
            "fake-words", axes,
            scored=config.scoring == "classic" and postings_bits == 0,
            vectors=keep_vectors,
            vq=quantized_store,
            tf=not (config.scoring == "dot" and postings_bits == 4),
            pq=doc if quant else None,
        )
    if isinstance(config, LexicalLshConfig):
        return _pspec_tree(
            "lexical-lsh", axes, vectors=keep_vectors, vq=quantized_store
        )
    if isinstance(config, KdTreeConfig):
        if config.backend == "tree":
            raise ValueError(_TREE_BACKEND_MSG)
        red = (
            pca.PcaModel(mean=P(), components=P())
            if config.reduction == "pca"
            else pca.PpaPcaPpaModel(
                ppa1=pca.PpaModel(mean=P(), top=P()),
                pca=pca.PcaModel(mean=P(), components=P()),
                ppa2=pca.PpaModel(mean=P(), top=P()),
            )
        )
        return _pspec_tree(
            "kd-tree", axes, vectors=keep_vectors, reduction_spec=red,
            vq=quantized_store,
        )
    if isinstance(config, BruteForceConfig):
        # fp32 vectors stay unless quantized postings replace them and no
        # exact rerank store asked to keep them (mirrors FlatPostings).
        return _pspec_tree(
            "bruteforce", axes,
            vectors=postings_bits == 0 or keep_vectors,
            vq=quantized_store,
            pq=doc if postings_bits > 0 else None,
        )
    if isinstance(config, GraphConfig):
        # The unit rows are the match operand: always present (like the
        # brute-force store), whatever the rerank-store choice.
        return _pspec_tree("hnsw", axes, vq=quantized_store)
    raise TypeError(f"unknown config {type(config)}")


# --------------------------------------------------------------------------
# Distributed build
# --------------------------------------------------------------------------


def build_sharded(
    mesh: Mesh,
    vectors: jax.Array,
    config,
    axes: Sequence[str],
    keep_vectors: bool = True,
    rerank_store: Optional[str] = None,
    primary_postings: str = "fp32",
    postings_group: int = 32,
):
    """Build ANY encoding's index with its doc-sharded leaves distributed
    over ``axes`` — the pod-scale entry of the staged
    :class:`repro.core.builder.BuildPipeline` (docs/DESIGN.md §8).

    Fake-words and LSH postings are embarrassingly row-parallel; the k-d
    tree's reduction fits from psum'd global moments so every shard holds
    the identical (replicated) model; global statistics (df -> idf) psum.
    No stage materializes the full corpus on any shard, and the result
    matches :func:`repro.core.builder.BuildPipeline.build_local`
    bit-for-bit (fp-tolerance for the eigendecomposed reduction).

    ``rerank_store``: "exact" | "int8" | "none" (None derives from
    ``keep_vectors``).  ``primary_postings``: "fp32" | "int8" | "int4" —
    the packed primary-postings encoding, quantized row-locally per shard
    (bitwise identical to the single-node build; docs/DESIGN.md §12)."""
    from repro.core import builder

    if rerank_store is None:
        rerank_store = "exact" if keep_vectors else "none"
    bp = builder.make_build_pipeline(
        config, rerank_store, primary_postings, postings_group
    )
    return bp.build_sharded(mesh, vectors, tuple(axes))


def build_fakewords_sharded(
    mesh: Mesh,
    vectors: jax.Array,
    config: FakeWordsConfig,
    axes: Sequence[str],
    keep_vectors: bool = True,
) -> FakeWordsIndex:
    """Deprecated alias: the fake-words special case of the generic
    :func:`build_sharded` (kept for callers of the pre-BuildPipeline
    API)."""
    return build_sharded(mesh, vectors, config, axes, keep_vectors)


# --------------------------------------------------------------------------
# Distributed search
# --------------------------------------------------------------------------


def merge_global_depth(
    index,
    loc_s: jax.Array,
    loc_i: jax.Array,
    gid: jax.Array,
    queries: jax.Array,
    axes: Sequence[str],
    k: int,
    depth: int,
    rerank: bool,
    quantized: bool = False,
):
    """Per-shard body of the one sharded merge, under ``shard_map``.

    ``loc_s``/``loc_i`` are this shard's local match top list ((B, d) scores
    and local rows, -1 = none) and ``gid`` the global doc ids of those rows.
    One all-gather of (score, id) gives every shard the same global
    top-``depth`` by match score, ties to the lower global id as on one
    device.  With ``rerank`` each shard scores the candidates it owns from
    its local store (-inf elsewhere), and a max over shards of that (B,
    depth) array gives the exact scores in the global candidate order; the
    top-``k`` of them is replicated on every shard."""
    axes = tuple(axes)
    d_loc = loc_s.shape[1]
    all_s = jax.lax.all_gather(loc_s, axes, axis=1, tiled=True)
    all_g = jax.lax.all_gather(gid, axes, axis=1, tiled=True)
    pos = jax.lax.broadcasted_iota(jnp.int32, all_s.shape, 1)
    d = min(depth, all_s.shape[1])
    neg, top_g, top_p = jax.lax.sort((-all_s, all_g, pos), dimension=1, num_keys=2)
    top_s, top_g, top_p = -neg[:, :d], top_g[:, :d], top_p[:, :d]
    k = min(k, d)
    if not rerank:
        return top_s[:, :k], top_g[:, :k]
    own = (top_p // d_loc == flat_axis_index(axes)) & (top_g >= 0)
    rows = jnp.take_along_axis(loc_i, top_p % d_loc, axis=1)
    rs = pl.candidate_scores(index, queries, jnp.where(own, rows, -1), quantized)
    rs = jax.lax.pmax(rs, axes)
    out_s, pos2 = jax.lax.top_k(rs, k)
    return out_s, jnp.take_along_axis(top_g, pos2, axis=1)


def merge_bytes(batch: int, depth: int, shards: int, rerank: bool) -> int:
    """Bytes each chip receives from the merge's collectives in one launch:
    the all-gathered (score, id) pairs of every shard, plus the (B, depth)
    rerank scores reduced over shards.  0 on one device."""
    if shards <= 1:
        return 0
    return batch * depth * (8 * shards + (4 if rerank else 0))


def make_sharded_search(
    mesh: Mesh,
    config,
    axes: Sequence[str],
    k: int = 10,
    depth: int = 100,
    rerank: bool = True,
    keep_vectors: bool = True,
    score_tile: int = 262_144,
    tile_unroll: bool = False,
    use_kernel: Optional[bool] = None,
    blockmax_keep: Optional[int] = None,
    rerank_store: Optional[str] = None,
    postings_bits: int = 0,
    filtered: bool = False,
):
    """Returns a jit-able ``search(index, q_rep, queries) -> (scores, ids)``
    closed over the mesh, for ANY method config (fake words / lexical LSH /
    kd-scan / brute force).  ``index`` leaves must be doc-sharded (see
    :func:`shard_index` / :func:`build_fakewords_sharded`); ``q_rep`` is the
    method's replicated query representation (encode outside the mesh with
    ``AnnIndex.encode_queries`` or the pipeline's encoder).

    The local match phase IS the method's pipeline matcher stage
    (:func:`repro.core.pipeline.make_matcher`) running on each shard's local
    slice: with ``use_kernel`` (the default on TPU) that's the fused
    streaming score->top-k Pallas kernel (docs/DESIGN.md §4); otherwise the
    XLA realization, which for fake-words shards larger than ``score_tile``
    docs streams tile-by-tile with a running top-d merge.

    With ``blockmax_keep`` set (fake-words / LSH), the returned callable
    becomes ``search(index, bm, q_rep, queries)`` (``bm`` built by
    ``blockmax.build_blockmax`` and placed by :func:`shard_blockmax`): each
    shard runs the two-stage pruned match through the
    :class:`repro.core.pipeline.BlockMaxMatcher` stage — bound pass over its
    local block upper bounds, then exact scoring of the kept blocks through
    the fused gathered streaming top-k kernel — so the pod also gets the
    ~(1 - beta) scan-byte cut.  The df-prune mask is not applied on this
    path (like the single-node ``pruned_search``).

    ``rerank_store`` ("exact" | "int8" | "none"; None derives from
    ``keep_vectors``) must name the store the index was built with: with
    "int8" the local rerank gathers from the int8
    :class:`repro.core.types.QuantizedStore` (~4x fewer HBM gather bytes
    per shard, docs/DESIGN.md §8) instead of the fp32 originals.

    ``filtered=True`` appends a trailing ``filt`` argument — a (N,) per-doc
    predicate bitmap (nonzero = keep) sharded WITH the postings on the doc
    dimension (``P(axes)``): each shard slices its own bits and threads
    them into the matcher's single in-kernel filtered pass
    (docs/DESIGN.md §13), so the bitmap never replicates and no
    cross-shard traffic is added beyond the existing (score, id) gather."""
    axes = tuple(axes)
    from repro.kernels.fused_topk import ops as fused

    if isinstance(config, GraphConfig):
        raise TypeError(
            "graph search cannot run shard-local: adjacency edges cross "
            "shard boundaries, so per-shard traversal + merge is not the "
            "same algorithm.  Serve graphs segmented "
            "(SegmentedAnnIndex) or single-device; the sharded BUILD "
            "(build_sharded) is supported and returns doc-sharded leaves "
            "you can all-gather onto one device."
        )
    if rerank_store is None:
        rerank_store = "exact" if keep_vectors else "none"
    if rerank and rerank_store == "none" and not isinstance(config, BruteForceConfig):
        raise ValueError("rerank=True needs rerank_store 'exact' or 'int8'")
    kernel_local = fused.resolve_use_kernel(use_kernel)
    matcher = pl.make_matcher(config, score_tile=score_tile, tile_unroll=tile_unroll)

    quantized = rerank_store == "int8"

    def merge_global(index, loc_s, loc_i, queries):
        # Global ids of a contiguous doc-sharded corpus: local row + this
        # shard's offset; invalid slots keep id -1.
        gid = jnp.where(loc_i >= 0, loc_i + flat_axis_index(axes) * index.num_docs, -1)
        return merge_global_depth(
            index, loc_s, loc_i, gid, queries, axes, k, depth, rerank, quantized
        )

    def local_search(index, q_rep, queries, filt=None):
        loc_s, loc_i = matcher(
            index, q_rep, depth, use_kernel=kernel_local, filt=filt
        )
        return merge_global(index, loc_s, loc_i, queries)

    def local_search_blockmax(index, bm, q_rep, queries, filt=None):
        n_keep = min(blockmax_keep, bm.num_blocks)
        # Cap on gathered candidates, NOT n_local: a ragged shard whose kept
        # blocks carry padded rows legitimately returns -1 slots when depth
        # exceeds its valid candidate count (merge_global masks them).
        d_local = min(depth, n_keep * bm.block_size)
        loc_s, loc_i = pl.BlockMaxMatcher(n_keep=n_keep)(
            index, q_rep, d_local, bm=bm, use_kernel=kernel_local, filt=filt
        )
        return merge_global(index, loc_s, loc_i, queries)

    index_spec = config_pspec(
        config, axes,
        keep_vectors=rerank_store == "exact",
        quantized_store=rerank_store == "int8",
        postings_bits=postings_bits,
    )
    if blockmax_keep is not None:
        # Prefix spec: BlockMaxIndex's one array leaf (ub) shards on the
        # block dimension; its block_size/mode are static metadata.
        in_specs = (index_spec, P(axes, None), P(), P())
        body = local_search_blockmax
    else:
        in_specs = (index_spec, P(), P())
        body = local_search
    if filtered:
        # The (N,) bitmap shards exactly like the doc rows it annotates.
        in_specs = in_specs + (P(axes),)
    # After the full all-gather + top_k the outputs are bitwise-replicated,
    # but the static VMA checker cannot prove it; disable the check.
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def build_blockmax_sharded(
    mesh: Mesh,
    index,
    axes: Sequence[str],
    block_size: int = 256,
    mode: Optional[str] = None,
    signed_store: bool = False,
) -> BlockMaxIndex:
    """Per-shard block upper bounds over an already-sharded index
    (fake-words or LSH).

    Each shard blocks ITS OWN doc range (padding its last block locally), so
    local block ids always line up with local doc rows and no global
    ``n_local % block_size`` alignment is required — a shard whose doc count
    is ragged against the block size simply carries out-of-range row ids in
    its padded tail, which the pruned stage-2 masks to (-inf, -1)."""
    from repro.core import blockmax as bmx

    axes = tuple(axes)

    def local_build(idx) -> BlockMaxIndex:
        return bmx.build_blockmax(
            idx, block_size, mode=mode, signed_store=signed_store
        )

    fn = jax.shard_map(
        local_build,
        mesh=mesh,
        in_specs=(index_pspec(index, axes),),
        out_specs=P(axes, None),  # prefix: the one array leaf (ub)
    )
    # Under the mesh context so a mesh with Explicit axes (jax.make_mesh's
    # default) can run the local gathers as well as an Auto one.
    with jax.set_mesh(mesh):
        return fn(index)


def shard_blockmax(
    mesh: Mesh, bm: BlockMaxIndex, axes: Sequence[str]
) -> BlockMaxIndex:
    """Place block upper bounds onto the mesh, block rows sharded like the
    doc dimension.  Blocks must not straddle shards: the local doc count has
    to be a multiple of ``block_size`` (then global block b lives exactly on
    shard ``b // n_blocks_local`` and local block ids line up with local doc
    rows)."""
    axes = tuple(axes)
    n_shards = flat_axis_size(mesh, axes)
    assert bm.ub.shape[0] % n_shards == 0, (
        f"{bm.ub.shape[0]} blocks not divisible by {n_shards} shards "
        "(need n_local % block_size == 0)"
    )
    return BlockMaxIndex(
        ub=jax.device_put(bm.ub, NamedSharding(mesh, P(axes, None))),
        block_size=bm.block_size,
        mode=bm.mode,
    )


def shard_index(mesh: Mesh, index, axes: Sequence[str]):
    """Place a host-built index (any type) onto the mesh with doc-dimension
    sharding; replicated stats / reduction models stay replicated."""
    specs = index_pspec(index, tuple(axes))
    return jax.tree_util.tree_map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        specs,
        index,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------------------------
# The doc-sharded writer's mesh (docs/DESIGN.md §5); its per-chip packed
# views and their search live in core/packed.py
# --------------------------------------------------------------------------

#: Mesh axis of a doc-sharded ``IndexWriter(shards=N)``.
SHARD_AXES = ("shard",)


def device_mesh(shards: int) -> Mesh:
    """A 1-D Auto-axis mesh over the first ``shards`` local devices."""
    devices = jax.local_devices()
    if len(devices) < shards:
        raise ValueError(
            f"shards={shards} needs {shards} local devices; found {len(devices)}"
        )
    return Mesh(
        np.array(devices[:shards]), SHARD_AXES,
        axis_types=(jax.sharding.AxisType.Auto,),
    )
