"""Staged index construction: one build architecture for every encoding
(docs/DESIGN.md §8) — the build-side mirror of ``core/pipeline.py``.

The paper's three Lucene encodings and the brute-force oracle share one
logical build recipe:

    normalize rows -> transform vectors     (tf rows / MinHash signatures /
                                             fitted reduction -> points)
                   -> assemble postings     (index container + global stats)
                   -> attach rerank store   (fp32 originals / int8+scale /
                                             none)

A :class:`BuildPipeline` makes that recipe structural.  Each stage is a
frozen (hashable, jit-static) dataclass:

  * **VectorTransform** — ``transform(v_norm, axes=None, n_total=None) ->
    (realization, fitted_model_or_None)``: the method's document
    realization.  Row-local for fake words (quantized tf rows), lexical LSH
    (MinHash signatures) and brute force (identity); the k-d tree's
    reduction fits from ``psum``-able moments (``core/pca.py``) so with
    ``axes`` set every shard fits the IDENTICAL model from global
    statistics while its rows stay shard-resident.
  * **Postings** — ``postings(realization, model, v_norm, store, n_total,
    axes=None) -> index``: assembles the index container.  Global
    statistics (fake-words df -> idf) are ``psum``-ed under ``axes`` so a
    sharded build matches the single-host build bit-for-bit.
  * **RerankStore** — ``store(v_norm) -> {"vectors": ..., "vq": ...}``: the
    exact-rerank operand.  :class:`ExactRerankStore` keeps the fp32
    originals; :class:`QuantizedRerankStore` keeps an int8 + per-doc-scale
    :class:`repro.core.types.QuantizedStore` (~4x fewer rerank gather
    bytes, score error bounded by ``||q||_1 * scale/2``);
    :class:`NoRerankStore` keeps neither.  Row-local, so it shards freely.

Because every stage takes ``axes`` explicitly, the SAME pipeline object
builds single-host (``build_local``) or row-parallel under ``shard_map``
over a mesh (``build_sharded``) — no stage ever materializes the full
corpus on one shard, and the per-method ``build()`` functions are thin
wrappers over these stages (exact parity), the same way PR 3's
SearchPipeline absorbed the per-method ``search()`` functions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bruteforce, pca
from repro.core.types import (
    BruteForceConfig,
    DocMetadata,
    FakeWordsConfig,
    FakeWordsIndex,
    FlatIndex,
    GraphConfig,
    GraphIndex,
    KdTreeConfig,
    KdTreeIndex,
    LexicalLshConfig,
    LshIndex,
    QuantizedPostings,
    QuantizedStore,
)

AnyConfig = Union[
    FakeWordsConfig, LexicalLshConfig, KdTreeConfig, BruteForceConfig,
    GraphConfig,
]

RERANK_STORES = ("exact", "int8", "none")
PRIMARY_POSTINGS = ("fp32", "int8", "int4")
POSTINGS_GROUPS = (32, 64)

_QUANT_POSTINGS_MSG = (
    "quantized primary postings support fake-words (classic/dot) and "
    "brute-force; the LSH signature store is categorical (uint32 MinHash "
    "buckets — scaling them is meaningless), the kd-tree reduced store "
    "is already ~8 f32 columns with a mixed-magnitude L2-lift column, and "
    "the graph matcher gathers tiny neighbor blocks (bytes moved scale "
    "with beam*degree, not N — use rerank_store='int8' for the memory "
    "knob instead) (docs/DESIGN.md §12)"
)

_TREE_BUILD_MSG = (
    "kd-tree 'tree' backend builds host-side (numpy) and cannot shard on "
    "documents; use backend='scan' (identical results, docs/DESIGN.md §3)"
)


# --------------------------------------------------------------------------
# Vector transforms
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TfTransform:
    """Fake words: sign-split quantized term-frequency rows (row-local)."""

    config: FakeWordsConfig

    def __call__(self, v: jax.Array, axes=None, n_total=None):
        from repro.core import fakewords

        return fakewords.encode(v, self.config.quantization, self.config.store_dtype), None


@dataclasses.dataclass(frozen=True)
class MinHashTransform:
    """Lexical LSH: MinHash signatures (row-local)."""

    config: LexicalLshConfig

    def __call__(self, v: jax.Array, axes=None, n_total=None):
        from repro.core import lexical_lsh

        return lexical_lsh.encode(v, self.config), None


@dataclasses.dataclass(frozen=True)
class ReductionTransform:
    """k-d tree: fit PPA/PCA from (psum-able) global moments, project rows.
    The fitted model rides along as the transform's aux output and lands in
    the index pytree (queries project through it at search time)."""

    config: KdTreeConfig

    def __call__(self, v: jax.Array, axes=None, n_total=None):
        model, reduced = pca.fit_reduction(
            v, self.config.dims, self.config.reduction, self.config.ppa_remove,
            axes=axes, n_total=n_total,
        )
        return reduced.astype(jnp.float32), model


@dataclasses.dataclass(frozen=True)
class IdentityTransform:
    """Brute force: the unit-normalized rows themselves."""

    def __call__(self, v: jax.Array, axes=None, n_total=None):
        return v, None


# --------------------------------------------------------------------------
# Primary-postings quantization (docs/DESIGN.md §12)
# --------------------------------------------------------------------------


def quantize_postings(
    mat: jax.Array, bits: int = 8, group: int = 32
) -> QuantizedPostings:
    """Quantize a posting matrix row-locally (shards and segments freely).

    bits=8: symmetric per-doc scale = max|row|/127, q = round(mat/scale)
    int8.  Because the scale is constant per row it factorizes out of the
    query dot, so dequantization is ONE multiply per (query, doc) after the
    reduction — the fused kernel applies it at merge time.

    bits=4: grouped scale over ``group`` consecutive columns (the term/dim
    axis is zero-padded to a multiple of ``group`` first, so groups align);
    scale = max|group|/7, nibble = clip(round(v/scale), -8, 7) + 8, adjacent
    column pairs packed low|high into one uint8.  Zero pad columns encode as
    nibble 8 and dequantize to exactly 0.  Per-element reconstruction error
    is bounded by scale/2 (round-to-nearest within a covered range).
    """
    m = mat.astype(jnp.float32)
    n, t = m.shape
    if bits == 8:
        amax = jnp.max(jnp.abs(m), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.round(m / scale).astype(jnp.int8)
        return QuantizedPostings(q=q, scale=scale, bits=8, group=0, cols=t)
    assert bits == 4, f"bits must be 8 or 4, got {bits}"
    tg = ((t + group - 1) // group) * group
    if tg != t:
        m = jnp.pad(m, ((0, 0), (0, tg - t)))
    grouped = m.reshape(n, tg // group, group)
    amax = jnp.max(jnp.abs(grouped), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 7.0  # (n, tg/group) f32
    nib = jnp.clip(jnp.round(grouped / scale[:, :, None]), -8, 7) + 8
    nib = nib.reshape(n, tg).astype(jnp.uint8)
    packed = (nib[:, 0::2] | (nib[:, 1::2] << 4)).astype(jnp.uint8)
    return QuantizedPostings(q=packed, scale=scale, bits=4, group=group, cols=t)


def dequantize_postings(pq: QuantizedPostings, dtype=jnp.float32) -> jax.Array:
    """Reconstruct the (N, cols) posting matrix in ``dtype``.

    Runs the CANONICAL dequant ordering (``repro.kernels.common``) both the
    Pallas kernel and the XLA reference scoring paths implement: f32
    (nibble - 8) * group_scale (int4) / f32 value * doc_scale (int8), THEN
    cast to the compute dtype.  Materializes the full matrix — for blockmax
    bounds / tests / error analysis, never on the streaming read path.
    """
    from repro.kernels import common

    if pq.bits == 8:
        return (pq.q.astype(jnp.float32) * pq.scale).astype(dtype)
    deq = common.dequant_int4(pq.q, pq.scale, pq.group, dtype)
    return deq[:, : pq.cols]


@dataclasses.dataclass(frozen=True)
class PostingsQuantizer:
    """BuildPipeline quantize stage: packs the method's match-stage posting
    matrix (classic ``scored`` / dot ``tf`` / brute-force vectors) into a
    :class:`QuantizedPostings` store.  Row-local, so it shards freely."""

    bits: int = 8
    group: int = 32

    def __call__(self, mat: jax.Array) -> QuantizedPostings:
        return quantize_postings(mat, self.bits, self.group)


# --------------------------------------------------------------------------
# Postings assembly
# --------------------------------------------------------------------------


def live_df(tf: jax.Array, live: Optional[jax.Array] = None) -> jax.Array:
    """Per-term document frequency over the (optionally live-masked) rows.
    Integer sum, so accumulating it per shard (psum) or per segment
    (docs/DESIGN.md §11) matches the single-host count bit-for-bit."""
    present = tf > 0
    if live is not None:
        present = present & live[:, None]
    return jnp.sum(present, axis=0).astype(jnp.int32)


def idf_from_df(df: jax.Array, n_total) -> jax.Array:
    """Lucene ClassicSimilarity idf = 1 + ln(N / (df + 1))."""
    return 1.0 + jnp.log(n_total / (df.astype(jnp.float32) + 1.0))


def classic_scored(tf: jax.Array, idf: jax.Array, norm: jax.Array) -> jax.Array:
    """Per-(doc, term) classic scoring matrix sqrt(tf_d)*idf^2*norm_d (bf16)
    so query scoring is one GEMM.  Row-local given idf: the ONE formula both
    the build stage and the segmented stats refresh (docs/DESIGN.md §11)
    evaluate, so a segment rescored under global statistics matches a
    monolithic build bit-for-bit."""
    tf_f = tf.astype(jnp.float32)
    return (jnp.sqrt(tf_f) * (idf**2)[None, :] * norm[:, None]).astype(
        jnp.bfloat16
    )


@dataclasses.dataclass(frozen=True)
class FakeWordsPostings:
    """df/idf/norm statistics + optional precomputed classic scoring matrix.
    df is the ONE global statistic: psum'd under ``axes`` (integer sum, so
    sharded idf/scored match the single-host build bit-for-bit).

    With a ``quantizer`` (docs/DESIGN.md §12) the match-stage store is
    packed AFTER the statistics: classic quantizes the scored matrix (df/idf
    are computed pre-quantization, so global scoring is unchanged) and drops
    the bf16 ``scored`` leaf; dot int8 is a no-op (the native int8 ``tf`` IS
    the int8 store); dot int4 packs ``tf`` and drops the leaf (``df`` then
    freezes Lucene-style until a merge rebuilds it)."""

    config: FakeWordsConfig
    quantizer: Optional[PostingsQuantizer] = None

    def __call__(self, tf, model, v, store, n_total, axes=None) -> FakeWordsIndex:
        df = live_df(tf)
        if axes is not None:
            df = jax.lax.psum(df, axes)
        idf = idf_from_df(df, n_total)
        doc_len = jnp.sum(tf.astype(jnp.float32), axis=-1)
        norm = jax.lax.rsqrt(jnp.maximum(doc_len, 1.0))
        scored = pq = None
        if self.config.scoring == "classic":
            scored = classic_scored(tf, idf, norm)
            if self.quantizer is not None:
                pq = self.quantizer(scored)
                scored = None
        elif self.quantizer is not None and self.quantizer.bits == 4:
            pq = self.quantizer(tf)
            tf = None
        return FakeWordsIndex(
            tf=tf, idf=idf, norm=norm, df=df, scored=scored, pq=pq, **store
        )


@dataclasses.dataclass(frozen=True)
class LshPostings:
    """Signatures carry their own statistics: pure container assembly."""

    def __call__(self, sig, model, v, store, n_total, axes=None) -> LshIndex:
        return LshIndex(sig=sig, **store)


@dataclasses.dataclass(frozen=True)
class KdTreePostings:
    """Reduced points + precomputed scan-kernel lift; the faithful tree
    arrays (backend='tree') are host-side numpy and local-build only."""

    config: KdTreeConfig

    def __call__(self, reduced, model, v, store, n_total, axes=None) -> KdTreeIndex:
        from repro.kernels.fused_topk import ops as fused

        split_dim = split_val = perm = None
        if self.config.backend == "tree":
            if axes is not None:
                raise ValueError(_TREE_BUILD_MSG)
            from repro.core import kdtree

            sd, sv, pm, _ = kdtree._build_arrays(
                np.asarray(reduced), self.config.leaf_size
            )
            split_dim, split_val, perm = (
                jnp.asarray(sd), jnp.asarray(sv), jnp.asarray(pm)
            )
        return KdTreeIndex(
            reduced=reduced,
            reduction=model,
            split_dim=split_dim,
            split_val=split_val,
            perm=perm,
            lifted=fused.lift_l2(reduced),
            **store,
        )


@dataclasses.dataclass(frozen=True)
class GraphPostings:
    """Flat proximity-graph stage (docs/DESIGN.md §15): exact-kNN candidate
    pools -> Vamana robust prune -> reverse-edge fill -> fixed-degree int32
    adjacency + entry points.  The unit rows are the match operand (neighbor
    blocks gather from them), so they are kept regardless of the rerank
    store, like :class:`FlatPostings`.  Under ``axes`` the candidate pools
    circulate the shard ring as neighbor-exchange rounds
    (``graph.build_graph_sharded``)."""

    config: GraphConfig

    def __call__(self, rep, model, v, store, n_total, axes=None) -> GraphIndex:
        from repro.core import graph

        if axes is None:
            neighbors, entry = graph.build_graph(v, self.config)
        else:
            neighbors, entry = graph.build_graph_sharded(
                v, self.config, axes=axes, n_total=n_total)
        return GraphIndex(
            vectors=v, neighbors=neighbors, entry=entry, vq=store["vq"]
        )


@dataclasses.dataclass(frozen=True)
class FlatPostings:
    """Brute force: the normalized rows ARE the match operand, so the exact
    fp32 vectors are kept regardless of the rerank-store choice — unless a
    ``quantizer`` replaces the match operand with packed int8/int4 postings
    (docs/DESIGN.md §12), in which case the fp32 rows survive only if the
    rerank store keeps them."""

    quantizer: Optional[PostingsQuantizer] = None

    def __call__(self, rep, model, v, store, n_total, axes=None) -> FlatIndex:
        if self.quantizer is None:
            return FlatIndex(vectors=v, vq=store["vq"])
        return FlatIndex(
            vectors=store["vectors"], vq=store["vq"], pq=self.quantizer(v)
        )


# --------------------------------------------------------------------------
# Metadata stage (docs/DESIGN.md §13)
# --------------------------------------------------------------------------


def build_metadata(metadata, n_docs: int) -> Optional[DocMetadata]:
    """Normalize the build-time ``metadata=`` argument into a
    :class:`repro.core.types.DocMetadata` store: ``None`` passes through, a
    ``{field: (N,) ints}`` mapping stacks into the (N, F) matrix, an
    existing DocMetadata is validated.  Row-local (doc-axis only), so it
    shards and segments exactly like the rerank stores."""
    if metadata is None:
        return None
    md = (
        metadata
        if isinstance(metadata, DocMetadata)
        else DocMetadata.from_fields(metadata)
    )
    if md.num_docs != n_docs:
        raise ValueError(
            f"metadata has {md.num_docs} rows but the corpus has {n_docs}"
        )
    return md


# --------------------------------------------------------------------------
# Rerank stores
# --------------------------------------------------------------------------


def quantize_store(v: jax.Array) -> QuantizedStore:
    """Symmetric per-doc int8 quantization: scale = max|v_row|/127,
    q = round(v/scale).  Row-local (shards freely)."""
    amax = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.round(v / scale[:, None]).astype(jnp.int8)
    return QuantizedStore(q=q, scale=scale.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class ExactRerankStore:
    """Keep the fp32 unit-normalized originals (the PR-3 default)."""

    def __call__(self, v: jax.Array) -> dict:
        return {"vectors": v, "vq": None}


@dataclasses.dataclass(frozen=True)
class QuantizedRerankStore:
    """int8 + per-doc scale instead of fp32 originals: ~4x fewer rerank
    gather bytes at a bounded score error (docs/DESIGN.md §8)."""

    def __call__(self, v: jax.Array) -> dict:
        return {"vectors": None, "vq": quantize_store(v)}


@dataclasses.dataclass(frozen=True)
class NoRerankStore:
    """No rerank operand (build-time opt-out; rerank=True will fail)."""

    def __call__(self, v: jax.Array) -> dict:
        return {"vectors": None, "vq": None}


_STORES = {
    "exact": ExactRerankStore(),
    "int8": QuantizedRerankStore(),
    "none": NoRerankStore(),
}


# --------------------------------------------------------------------------
# The pipeline
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BuildPipeline:
    """normalize -> transform -> postings -> rerank store.

    Frozen and hashable, like :class:`repro.core.pipeline.SearchPipeline`:
    a build pipeline is a static description of *how* to build; all array
    state flows through the call.  ``build_local`` and ``build_sharded``
    run the SAME stage objects — the only difference is ``axes`` (which
    turns the global-statistic reductions into psums under ``shard_map``).
    """

    config: AnyConfig
    transform: Any
    postings: Any
    store: Any = ExactRerankStore()

    def _assemble(self, v, n_total, axes=None):
        rep, model = self.transform(v, axes=axes, n_total=n_total)
        return self.postings(rep, model, v, self.store(v), n_total, axes=axes)

    def build_local(self, vectors: jax.Array, normalized: bool = False):
        """Single-host build (what the per-method ``build()`` wrappers
        call)."""
        v = jnp.asarray(vectors)
        v = v if normalized else bruteforce.l2_normalize(v)
        return self._assemble(v, n_total=v.shape[0])

    def sharded_build_fn(
        self, mesh, axes: Sequence[str], n_total: int, normalized: bool = False
    ):
        """The ``shard_map``-wrapped per-shard build: ``fn(vectors) ->
        index`` with doc-sharded leaves.  Reusable across calls (jit caches
        one compilation) — ``build_sharded`` is the one-shot convenience."""
        from repro.core import distributed

        axes = tuple(axes)
        if isinstance(self.config, KdTreeConfig) and self.config.backend == "tree":
            raise ValueError(_TREE_BUILD_MSG)

        def local_build(v):
            # Normalization is row-local, so honoring ``normalized`` here
            # keeps the sharded branch argument-for-argument equal to
            # build_local.
            v = v if normalized else bruteforce.l2_normalize(v)
            return self._assemble(v, n_total=n_total, axes=axes)

        quantizer = getattr(self.postings, "quantizer", None)
        out_specs = distributed.config_pspec(
            self.config, axes,
            keep_vectors=isinstance(self.store, ExactRerankStore)
            or (isinstance(self.config, BruteForceConfig) and quantizer is None),
            quantized_store=isinstance(self.store, QuantizedRerankStore),
            postings_bits=quantizer.bits if quantizer is not None else 0,
        )
        # Replicated leaves (idf/df, reduction model) come out of psums the
        # static replication checker cannot always prove; disable it — the
        # sharded==local parity tests are the real guarantee.
        return jax.shard_map(
            local_build, mesh=mesh, in_specs=jax.sharding.PartitionSpec(axes, None),
            out_specs=out_specs, check_vma=False,
        )

    def build_sharded(
        self,
        mesh,
        vectors: jax.Array,
        axes: Sequence[str],
        normalized: bool = False,
    ):
        """Row-parallel build under ``shard_map``: every doc-sharded leaf is
        computed from shard-local rows; global statistics (df, reduction
        moments) travel through psums.  No stage materializes the full
        corpus on any shard."""
        from repro.core import distributed

        n = vectors.shape[0]
        n_shards = distributed.flat_axis_size(mesh, tuple(axes))
        assert n % n_shards == 0, (
            f"corpus size {n} not divisible by {n_shards} shards"
        )
        return self.sharded_build_fn(mesh, axes, n, normalized=normalized)(vectors)

    def build(
        self,
        vectors: jax.Array,
        mesh=None,
        axes: Sequence[str] = ("data",),
        normalized: bool = False,
    ):
        """Single entry point: local when ``mesh`` is None, else sharded."""
        if mesh is None:
            return self.build_local(vectors, normalized=normalized)
        return self.build_sharded(mesh, vectors, axes, normalized=normalized)


def make_build_pipeline(
    config: AnyConfig,
    rerank_store: str = "exact",
    primary_postings: str = "fp32",
    postings_group: int = 32,
) -> BuildPipeline:
    """Every method is a stage configuration (the build-side analog of
    ``pipeline.build_pipeline``).  ``rerank_store``: "exact" | "int8" |
    "none".  ``primary_postings``: "fp32" (store the match operand as
    built) | "int8" (per-doc scale) | "int4" (grouped scale, group size
    ``postings_group`` in {32, 64}) — docs/DESIGN.md §12."""
    if rerank_store not in _STORES:
        raise ValueError(
            f"rerank_store must be one of {RERANK_STORES}, got {rerank_store!r}"
        )
    if primary_postings not in PRIMARY_POSTINGS:
        raise ValueError(
            f"primary_postings must be one of {PRIMARY_POSTINGS}, "
            f"got {primary_postings!r}"
        )
    store = _STORES[rerank_store]
    quantizer = None
    if primary_postings != "fp32":
        if isinstance(config, (LexicalLshConfig, KdTreeConfig, GraphConfig)):
            raise ValueError(_QUANT_POSTINGS_MSG)
        if postings_group not in POSTINGS_GROUPS:
            raise ValueError(
                f"postings_group must be one of {POSTINGS_GROUPS}, "
                f"got {postings_group}"
            )
        quantizer = PostingsQuantizer(
            bits=8 if primary_postings == "int8" else 4, group=postings_group
        )
    if isinstance(config, FakeWordsConfig):
        return BuildPipeline(
            config, TfTransform(config), FakeWordsPostings(config, quantizer),
            store,
        )
    if isinstance(config, LexicalLshConfig):
        return BuildPipeline(config, MinHashTransform(config), LshPostings(), store)
    if isinstance(config, KdTreeConfig):
        return BuildPipeline(config, ReductionTransform(config), KdTreePostings(config), store)
    if isinstance(config, BruteForceConfig):
        return BuildPipeline(
            config, IdentityTransform(), FlatPostings(quantizer), store
        )
    if isinstance(config, GraphConfig):
        return BuildPipeline(
            config, IdentityTransform(), GraphPostings(config), store
        )
    raise TypeError(f"unknown config {type(config)}")
