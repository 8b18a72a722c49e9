"""Block upper-bound pruning - WAND/BlockMax-WAND adapted to TPU tiles.

Lucene never scores documents that share no query term, and WAND-style
engines additionally skip whole postings blocks whose term-score upper bounds
cannot beat the current k-th best.  A dense GEMM scores everything, so we
recover the skipping *architecturally*: documents are grouped into fixed-size
blocks, each block stores per-term upper bounds, and at query time we

  1. score every block's upper bound with one small operation
     (n_blocks x T) against the query  ->  optimistic block scores,
  2. keep only the top ``beta``-fraction of blocks (static shape!),
  3. gather those blocks' rows and score them exactly — through the fused
     gathered streaming top-k kernel (docs/DESIGN.md §4), so the stage-2
     score matrix never materializes.

The bound structure generalizes over every scoring mode (docs/DESIGN.md §6):

  * classic — ub[b,t] = max over docs in block b of the precomputed
    ``scored`` entry (non-negative), bound = one small bf16 GEMM against the
    query tf row.  Exact-admissible.
  * dot     — per-term SIGNED doc values s = tf+ - tf- can be negative, so a
    single max is not admissible.  Store ub = [max(s); max(-s)] per block;
    because the sign-split query encoding satisfies q+ = relu(u) and
    q- = relu(-u) (a feature is positive or negative, never both), the bound
    is q_tf @ ub.T — still a single small GEMM via the ``[u; -u]`` lift.
  * lsh     — per-block per-slot presence bitmaps: bit (v & 31) of
    ``ub[b, s]`` is set iff some doc in block b holds MinHash value v in
    slot s.  The bound counts query slots whose value's bit is present —
    a superset test, so collisions only loosen the bound (admissible).

This turns the paper's "filter high-frequency terms" latency trick into a
second, stronger roofline lever: the index-scan GEMM is memory-bound, and
block pruning cuts its bytes by ~(1 - beta) at a small recall cost that the
benchmark sweeps (see docs/DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fakewords
from repro.core.types import FakeWordsIndex, LshIndex

AnyBlockIndex = Union[FakeWordsIndex, LshIndex]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockMaxIndex:
    """Per-block upper-bound structure, block = ``block_size`` consecutive
    docs.  ``ub`` layout depends on ``mode``:

      classic: (n_blocks, 2m) bf16 max of the scored matrix (exact bound);
      dot:     (n_blocks, 2m) int8 ``[max(s); max(-s)]`` over the signed
               per-term doc values s = tf+ - tf-;
      lsh:     (n_blocks, S) uint32 per-slot presence bitmaps.
    """

    ub: jax.Array
    block_size: int = dataclasses.field(metadata=dict(static=True))
    mode: str = dataclasses.field(default="classic", metadata=dict(static=True))

    @property
    def num_blocks(self) -> int:
        return self.ub.shape[0]


def _block_reduce_max(x: jax.Array, block_size: int, pad_value=0) -> jax.Array:
    n, t = x.shape
    n_pad = (-n) % block_size
    if n_pad:
        x = jnp.concatenate(
            [x, jnp.full((n_pad, t), pad_value, x.dtype)], axis=0
        )
    return jnp.max(x.reshape(-1, block_size, t), axis=1)


def _dequantized_f32(pq) -> jax.Array:
    """f32 effective per-element values of a packed postings store, matching
    the score stage's arithmetic (docs/DESIGN.md §12): int8 contributes
    ``scale * q`` as an exact f32 product; int4 contributes the bf16-cast
    canonical dequant (the actual kernel operand), widened to f32.  Block
    maxima over THESE values give admissible bounds on quantized scores."""
    from repro.kernels import common

    if pq.bits == 8:
        return pq.q.astype(jnp.float32) * pq.scale
    deq = common.dequant_int4(pq.q, pq.scale, pq.group, jnp.bfloat16)
    return deq[:, : pq.cols].astype(jnp.float32)


def _lsh_block_bitmap(sig: jax.Array, block_size: int) -> jax.Array:
    from repro.core import lexical_lsh

    n, s = sig.shape
    n_pad = (-n) % block_size
    if n_pad:
        sig = jnp.concatenate(
            [sig, jnp.full((n_pad, s), lexical_lsh.SENTINEL, sig.dtype)], axis=0
        )
    bits = jnp.where(
        sig != lexical_lsh.SENTINEL,
        jnp.left_shift(jnp.uint32(1), sig & jnp.uint32(31)),
        jnp.uint32(0),
    )
    blocks = bits.reshape(-1, block_size, s)
    return jax.lax.reduce(blocks, np.uint32(0), jax.lax.bitwise_or, (1,))


def build_blockmax(
    index: AnyBlockIndex,
    block_size: int = 256,
    mode: Optional[str] = None,
    signed_store: bool = False,
) -> BlockMaxIndex:
    """Build per-block upper bounds for any index / scoring mode.

    ``mode`` defaults to "lsh" for an LshIndex, else "classic" when the
    FakeWordsIndex carries a ``scored`` matrix and "dot" otherwise.
    ``signed_store`` marks a dot-mode index whose ``tf`` already holds the
    SIGNED (N, m) matrix (FakeWordsConfig.signed_store)."""
    if isinstance(index, LshIndex) or mode == "lsh":
        return BlockMaxIndex(
            ub=_lsh_block_bitmap(index.sig, block_size),
            block_size=block_size, mode="lsh",
        )
    if mode is None:
        # A packed store alongside tf is quantized-classic (dot-int4 drops
        # tf; dot-int8 stores quantized tf natively with no pq leaf).
        classic = index.scored is not None or (
            index.pq is not None and index.tf is not None
        )
        mode = "classic" if classic else "dot"
    if mode == "classic":
        if index.pq is not None:
            # Bounds from the DEQUANTIZED maxima, f32: per-doc/group scales
            # vary inside a block, so max does not commute with dequant.
            return BlockMaxIndex(
                ub=_block_reduce_max(_dequantized_f32(index.pq), block_size),
                block_size=block_size, mode="classic",
            )
        assert index.scored is not None, "classic blockmax requires scored matrix"
        return BlockMaxIndex(
            ub=_block_reduce_max(index.scored, block_size),
            block_size=block_size, mode="classic",
        )
    assert mode == "dot", f"unknown blockmax mode {mode}"
    if index.pq is not None:
        deq = _dequantized_f32(index.pq)  # (N, m) signed or (N, 2m) split
        if deq.shape[1] * 2 == index.df.shape[0]:
            s = deq  # hand-built signed packed store, already (N, m)
        else:
            m = deq.shape[1] // 2
            s = deq[:, :m] - deq[:, m:]
        ub = jnp.concatenate(
            [_block_reduce_max(s, block_size), _block_reduce_max(-s, block_size)],
            axis=-1,
        )
        return BlockMaxIndex(ub=ub, block_size=block_size, mode="dot")
    tf = index.tf
    if signed_store:
        s = tf.astype(jnp.int8)
    else:
        # df spans the logical 2m columns; a packed tf may be lane-aligned.
        m = index.df.shape[0] // 2
        s = (
            tf[:, :m].astype(jnp.int32) - tf[:, m : 2 * m].astype(jnp.int32)
        ).astype(jnp.int8)
    ub = jnp.concatenate(
        [_block_reduce_max(s, block_size), _block_reduce_max(-s, block_size)],
        axis=-1,
    )
    return BlockMaxIndex(ub=ub, block_size=block_size, mode="dot")


def block_bounds(bm: BlockMaxIndex, q: jax.Array) -> jax.Array:
    """Stage 1: (B, n_blocks) optimistic block score upper bounds.

    ``q`` is the mode's match-phase query representation: the (B, 2m) tf row
    for classic AND dot (the dot bound's ``[relu(u); relu(-u)]`` operand IS
    the sign-split encoding), or the (B, S) uint32 signature for lsh.
    Bounds built over a lane-aligned packed view are as wide as the stored
    leaf; the query is widened to match (exact: zero or sentinel pads)."""
    from repro.kernels.fused_topk import ops as fused

    q = fused.pad_query(q, bm.ub.shape[-1])
    if bm.mode == "classic":
        return jnp.einsum(
            "bt,nt->bn", q.astype(jnp.bfloat16), bm.ub,
            preferred_element_type=jnp.float32,
        )
    if bm.mode == "dot":
        if jnp.issubdtype(bm.ub.dtype, jnp.floating):
            # Quantized store: dequantized maxima are f32, not int8.
            return jnp.einsum(
                "bt,nt->bn", q.astype(jnp.float32), bm.ub,
                preferred_element_type=jnp.float32,
            )
        return jnp.einsum(
            "bt,nt->bn", q.astype(jnp.int32), bm.ub.astype(jnp.int32),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    from repro.core import lexical_lsh

    member = (bm.ub[None, :, :] >> (q & jnp.uint32(31))[:, None, :]) & jnp.uint32(1)
    valid = (q != lexical_lsh.SENTINEL)[:, None, :]
    return jnp.sum(
        jnp.where(valid, member, jnp.uint32(0)), axis=-1, dtype=jnp.int32
    ).astype(jnp.float32)


def _stage2_operands(
    index: AnyBlockIndex, bm: BlockMaxIndex, q: jax.Array
) -> Tuple[jax.Array, jax.Array, str]:
    """(query operand, stored matrix to gather from, kernel mode).  With a
    packed postings store the matrix slot carries the
    :class:`repro.core.types.QuantizedPostings` itself and the mode is
    "quantized" — stage 2 gathers packed rows + scales and dequantizes in
    the score stage."""
    pq = getattr(index, "pq", None)
    if bm.mode == "classic":
        if pq is not None:
            return q.astype(jnp.bfloat16), pq, "quantized"
        return q.astype(jnp.bfloat16), index.scored, "gemm"
    if bm.mode == "dot":
        m = bm.ub.shape[1] // 2
        u = fakewords.signed_query(q)
        if pq is not None:
            if pq.cols == m:  # signed store: packed matrix already (N, m)
                return u.astype(jnp.bfloat16), pq, "quantized"
            return (
                jnp.concatenate([u, -u], axis=-1).astype(jnp.bfloat16),
                pq, "quantized",
            )
        if index.tf.shape[1] == m:  # signed store: tf already (N, m) signed
            return u.astype(jnp.int8), index.tf, "gemm"
        return jnp.concatenate([u, -u], axis=-1).astype(jnp.int8), index.tf, "gemm"
    return q, index.sig, "lsh"


def pruned_topk(
    index: AnyBlockIndex,
    bm: BlockMaxIndex,
    q: jax.Array,
    n_keep: int,
    depth: int,
    use_kernel: Optional[bool] = None,
    filt: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Two-stage blockmax search core (un-jitted: usable inside shard_map).

    ``n_keep`` is clamped to the block count and ``depth`` to the gathered
    candidate count (the former crashed ``lax.top_k`` and the latter the
    gathered top-k before); when clamped, the output is padded back to the
    requested ``depth`` with (-inf, -1) so shapes stay caller-visible.

    ``filt`` is a per-doc predicate bitmap ((N,) | (B, N), nonzero = keep)
    masked inside the stage-2 gathered score pass.  Stage-1 bounds stay
    UNfiltered: filtering only removes docs, so an unfiltered block maximum
    remains an admissible overestimate — at beta=1.0 every block is kept
    and the filtered result equals the dense filtered paths exactly."""
    from repro.kernels.fused_topk import ops as fused
    from repro.kernels.fused_topk import ref as fused_ref

    bsz = bm.block_size
    n_keep = min(n_keep, bm.num_blocks)
    eff_depth = min(depth, n_keep * bsz)
    n_docs = index.num_docs
    b = q.shape[0]

    _, keep_blocks = jax.lax.top_k(block_bounds(bm, q), n_keep)  # (B, n_keep)
    row_ids = keep_blocks[:, :, None] * bsz + jnp.arange(bsz)[None, None, :]
    row_ids = row_ids.reshape(b, -1).astype(jnp.int32)  # (B, n_keep*bsz)
    qv, mat, mode = _stage2_operands(index, bm, q)
    if mode != "quantized":
        qv = fused.pad_query(qv, mat.shape[-1])  # lane-aligned packed leaf
    if mode == "quantized":
        if fused.resolve_use_kernel(use_kernel):
            d_s, d_i = fused.postings_topk_gathered(
                mat, qv, row_ids, eff_depth, n_docs, filt=filt
            )
        else:
            safe = jnp.minimum(row_ids, n_docs - 1)
            d_s, d_i = fused_ref.quantized_gathered_topk_ref(
                qv, mat.q[safe], mat.scale[safe], row_ids, eff_depth,
                n_docs, mat.bits, mat.group,
                filt=fused.gather_filt(filt, row_ids, n_docs),
            )
    elif fused.resolve_use_kernel(use_kernel):
        rows = mat[jnp.minimum(row_ids, n_docs - 1)]  # (B, R, T)
        d_s, d_i = fused.fused_topk_gathered(
            qv, rows, row_ids, eff_depth, n_docs, mode=mode,
            filt=fused.gather_filt(filt, row_ids, n_docs),
        )
    else:
        rows = mat[jnp.minimum(row_ids, n_docs - 1)]  # (B, R, T)
        d_s, d_i = fused_ref.gathered_topk_ref(
            qv, rows, row_ids, eff_depth, n_docs, mode=mode,
            filt=fused.gather_filt(filt, row_ids, n_docs),
        )
    if eff_depth < depth:
        pad = depth - eff_depth
        d_s = jnp.concatenate(
            [d_s, jnp.full((b, pad), -jnp.inf, d_s.dtype)], axis=-1
        )
        d_i = jnp.concatenate(
            [d_i, jnp.full((b, pad), -1, d_i.dtype)], axis=-1
        )
    return d_s, d_i


@functools.partial(jax.jit, static_argnames=("n_keep", "depth", "use_kernel"))
def pruned_search(
    index: AnyBlockIndex,
    bm: BlockMaxIndex,
    q_tf: jax.Array,
    n_keep: int,
    depth: int,
    use_kernel: Optional[bool] = None,
    filt: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Two-stage blockmax search: upper-bound pass -> keep n_keep blocks ->
    exact scoring on the gathered rows.  Returns (scores, doc_ids) at depth;
    works for classic, dot/int8 and LSH indexes (``bm.mode`` selects).

    ``use_kernel`` routes stage 2 through the fused gathered-candidates
    streaming top-k kernel (docs/DESIGN.md §4): the (B, n_keep*block_size)
    stage-2 score matrix never materializes.  Default: kernel on TPU.
    Ties break on the lowest doc id on both paths, so at beta=1.0 the ids
    equal the dense reference paths exactly.

    (:class:`repro.core.pipeline.BlockMaxMatcher` is the same two-stage
    match as a pipeline stage; this wrapper is the jitted standalone form.)"""
    return pruned_topk(index, bm, q_tf, n_keep, depth, use_kernel, filt=filt)
