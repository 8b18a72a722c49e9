"""Pallas TPU kernel: fused streaming score -> top-k (docs/DESIGN.md §4).

Every search hot path used to materialize a dense (B, N) f32 score matrix in
HBM and only then run ``jax.lax.top_k`` — at production corpus sizes the
score-matrix write+read dominates HBM traffic, not the index scan.  This
kernel applies the flash-attention online-reduction trick to retrieval: a
tiled GEMM over doc blocks keeps a per-query running top-``depth``
(scores + global doc ids) in VMEM scratch across the doc-tile grid axis, so
the only HBM traffic is the index stream plus an O(B * depth) result.

Score stages (selected by ``mode`` / operand dtypes):

  * gemm  — scores = q @ docs.T.  bf16 operands with f32 accumulate covers
    the classic-similarity path (q = tf_q * keep against the precomputed
    ``scored`` matrix); int8 operands with int32 accumulate cover the dot
    path (q lifted to [u; -u], the MXU's 4x-throughput integer pipe); f32
    covers brute-force cosine and the kd-tree reduced-space L2 lift.
  * lsh   — scores = MinHash collision counts (equality + popcount-style
    reduce on the VPU; sentinel-aware like ``lsh_match``).

Grid = (query tiles, doc tiles, reduce tiles); the reduce (K) axis is the
innermost "arbitrary" axis so the (bq, bn) accumulator carries across K
steps, and the doc axis is also "arbitrary" so the running top-``depth``
scratch carries across doc tiles.  After the last K step of each doc tile the
tile's scores are merged into the running best — a whole tile is skipped when
its best score cannot beat any query's current depth-th best (the dense-GEMM
analogue of WAND block skipping).  Two merge strategies (``merge``):

  * "bitonic" (default) — bitonic per-tile pre-reduction: a vectorized
    bitonic sort network (compare-exchange partners from lane rotations,
    no gathers or reshapes) sorts the tile under (score desc, id asc), its
    best ``dpad`` columns are kept, and one half-cleaner plus a bitonic
    merge fold them into the (sorted) running best.  O(log^2 bn + log
    depth) vectorized steps per tile instead of ``depth`` sequential
    max-extractions.
  * "extract" — the original exact iterative max-extraction (kept for A/B
    profiling; identical results).

Both strategies order ties by the minimum id, which equals ``jax.lax.top_k``'s
lowest-index tie-break because candidate ids are globally unique and id-sorted
in the dense variant; the gathered variant merges on GLOBAL doc ids so its tie
behavior matches the dense reference paths exactly.  Padded / ragged N is
masked to -inf inside the kernel, so callers can stream any corpus size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

# Sentinel id for empty / padded top-k slots (replaced by -1 on the host).
BIG_ID = np.int32(2**30)
LSH_SENTINEL = np.uint32(0xFFFFFFFF)

_INT_DTYPES = (jnp.int8, jnp.int32, jnp.uint32)


# --------------------------------------------------------------------------
# Bitonic sorting network (vectorized, gather-free)
# --------------------------------------------------------------------------


def _better(s, i, ps, pi):
    """(s, i) precedes (ps, pi) in the total order (score desc, id asc)."""
    return (s > ps) | ((s == ps) & (i < pi))


def _cmp_exchange(s, i, j: int, want_better):
    """One compare-exchange stage at stride ``j`` over lane axis 1.

    Lane ``x`` pairs with lane ``x ^ j``.  The partner's value comes from two
    lane rotations selected by an iota mask, never from a gather or a
    reshape.  ``want_better`` (bool, same shape) marks the lanes that must
    end up holding the better element of their pair; a lane takes its
    partner exactly when its own rank disagrees with that (an XOR, so no
    select between boolean vectors is needed).
    """
    n = s.shape[1]
    lower = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) & j) == 0
    ps = jnp.where(lower, pltpu.roll(s, n - j, 1), pltpu.roll(s, j, 1))
    pi = jnp.where(lower, pltpu.roll(i, n - j, 1), pltpu.roll(i, j, 1))
    take = _better(s, i, ps, pi) ^ want_better
    return jnp.where(take, ps, s), jnp.where(take, pi, i)


def _bitonic_sort_asc(s, i):
    """Full bitonic sort of (bq, L) pairs, worst first and best last under
    (score desc, id asc); L pow2."""
    n = s.shape[1]
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    k = 2
    while k <= n:
        asc = (idx & k) == 0
        j = k // 2
        while j >= 1:
            # Ascending block: the lower lane of a pair keeps the worse one.
            s, i = _cmp_exchange(s, i, j, ((idx & j) == 0) ^ asc)
            j //= 2
        k *= 2
    return s, i


def _bitonic_merge_desc(s, i):
    """Merge a (bq, L) bitonic sequence to sorted descending; L pow2."""
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    j = s.shape[1] // 2
    while j >= 1:
        s, i = _cmp_exchange(s, i, j, (idx & j) == 0)
        j //= 2
    return s, i


def _merge_topk_bitonic(rs_ref, ri_ref, tile_s, tile_i) -> None:
    """Bitonic per-tile pre-reduction merge.

    Sort the candidate tile ascending, so its best ``dpad`` columns are the
    static tail slice.  The running best is kept sorted descending (the
    init fill and this merge preserve it), so running ++ tail is bitonic:
    one half-cleaner (an elementwise better-of) keeps the best ``dpad`` of
    the union, and a bitonic merge sorts them descending again.  ``dpad``
    (the running width) is a power of two on this path.
    """
    bq, dpad = rs_ref.shape
    pad_to = max(common.next_pow2(tile_s.shape[1]), dpad)
    pad = pad_to - tile_s.shape[1]
    if pad:
        tile_s = jnp.concatenate(
            [tile_s, jnp.full((bq, pad), -jnp.inf, tile_s.dtype)], axis=1
        )
        tile_i = jnp.concatenate(
            [tile_i, jnp.full((bq, pad), BIG_ID, tile_i.dtype)], axis=1
        )
    tile_s, tile_i = _bitonic_sort_asc(tile_s, tile_i)
    top_s, top_i = tile_s[:, pad_to - dpad :], tile_i[:, pad_to - dpad :]
    run_s, run_i = rs_ref[...], ri_ref[...]
    take = _better(top_s, top_i, run_s, run_i)
    s, i = _bitonic_merge_desc(
        jnp.where(take, top_s, run_s), jnp.where(take, top_i, run_i)
    )
    rs_ref[...] = s
    ri_ref[...] = i


# --------------------------------------------------------------------------
# Iterative max-extraction merge (legacy strategy, kept for A/B profiling)
# --------------------------------------------------------------------------


def _merge_topk_extract(rs_ref, ri_ref, tile_s, tile_i, depth: int) -> None:
    """Merge a (bq, bn) candidate tile into the running (bq, depth) best.

    Exact iterative max-extraction over the concatenated candidates.  Ties
    select the minimum id, which equals ``jax.lax.top_k``'s lowest-index
    tie-break over id-ordered candidates.  Extracted entries are retired to
    (-inf, BIG_ID) so -inf padding can never resurrect a stale id.
    """
    run_s = rs_ref[:, :depth]
    run_i = ri_ref[:, :depth]
    comb_s = jnp.concatenate([run_s, tile_s], axis=1)
    comb_i = jnp.concatenate([run_i, tile_i], axis=1)
    init = (
        comb_s,
        comb_i,
        jnp.full_like(run_s, -jnp.inf),
        jnp.full_like(run_i, BIG_ID),
    )

    def extract(d, carry):
        cs, ci, ns, ni = carry
        best = jnp.max(cs, axis=1, keepdims=True)  # (bq, 1)
        sel = jnp.min(
            jnp.where(cs == best, ci, BIG_ID), axis=1, keepdims=True
        )  # (bq, 1) min id among argmaxes
        col = jax.lax.broadcasted_iota(jnp.int32, ns.shape, 1) == d
        ns = jnp.where(col, best, ns)
        ni = jnp.where(col, sel, ni)
        kill = (cs == best) & (ci == sel)
        cs = jnp.where(kill, -jnp.inf, cs)
        ci = jnp.where(kill, BIG_ID, ci)
        return cs, ci, ns, ni

    _, _, new_s, new_i = jax.lax.fori_loop(0, depth, extract, init)
    rs_ref[:, :depth] = new_s
    ri_ref[:, :depth] = new_i


def _merge_if_improves(
    rs_ref, ri_ref, tile_s, tile_i, depth: int, merge: str, strict: bool
) -> None:
    """WAND-style tile skip: merging is wasted work unless some query's tile
    best can beat its current depth-th best.  ``strict`` (dense variant) is
    exact because ids ascend across doc tiles, so ties lose to the running
    set's smaller ids; the gathered variant merges on UNORDERED global doc
    ids (blocks arrive in stage-1 bound order), where a tying tile may hold
    the smaller — winning — id, so it must compare with ``>=``."""
    thresh = jnp.min(rs_ref[:, :depth], axis=1)
    best = jnp.max(tile_s, axis=1)
    improves = jnp.any(best > thresh if strict else best >= thresh)

    @pl.when(improves)
    def _():
        if merge == "bitonic":
            _merge_topk_bitonic(rs_ref, ri_ref, tile_s, tile_i)
        else:
            _merge_topk_extract(rs_ref, ri_ref, tile_s, tile_i, depth)


def _score_tile(q, d, mode: str, acc_dtype):
    if mode == "lsh":
        eq = (q[:, None, :] == d[None, :, :]) & (q[:, None, :] != LSH_SENTINEL)
        return jnp.sum(eq.astype(jnp.int32), axis=-1)
    return jnp.dot(q, d.T, preferred_element_type=acc_dtype)


def _fused_topk_kernel(
    q_ref, d_ref, *refs,
    n_j: int, n_k: int, n_docs: int, bn: int, depth: int, mode: str,
    merge: str, acc_dtype, has_filt: bool = False,
):
    if has_filt:
        f_ref, s_ref, i_ref, acc_ref, rs_ref, ri_ref = refs
    else:
        f_ref = None
        s_ref, i_ref, acc_ref, rs_ref, ri_ref = refs
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_running():
        rs_ref[...] = jnp.full_like(rs_ref, -jnp.inf)
        ri_ref[...] = jnp.full_like(ri_ref, BIG_ID)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _score_tile(q_ref[...], d_ref[...], mode, acc_dtype)

    @pl.when(k == n_k - 1)
    def _merge():
        tile_s = acc_ref[...].astype(jnp.float32)
        ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, tile_s.shape, 1)
        valid = ids < n_docs  # ragged N: padded docs can never rank
        if has_filt:
            # Predicate bitmap applied INSIDE the streaming merge: filtered
            # docs score but can never rank, so the (B, N) matrix still
            # never exists and filtering costs one extra VPU AND per tile.
            valid = valid & (f_ref[...] != 0)
        tile_s = jnp.where(valid, tile_s, -jnp.inf)
        ids = jnp.where(valid, ids, BIG_ID)
        _merge_if_improves(rs_ref, ri_ref, tile_s, ids, depth, merge,
                           strict=True)

    @pl.when(jnp.logical_and(j == n_j - 1, k == n_k - 1))
    def _flush():
        s_ref[...] = rs_ref[...]
        i_ref[...] = ri_ref[...]


def _filt_operand(filt, bq: int, bn: int):
    """Normalize a per-doc predicate bitmap to a padded int32 kernel operand
    plus its BlockSpec.  Accepts (N,) (shared across the batch) or (B, N)
    (per-query); padding docs get 0 (already masked by the n_docs check,
    but keep the invariant anyway)."""
    f = filt.astype(jnp.int32)
    if f.ndim == 1:
        fp = common.pad_dim(f[None, :], 1, bn)
        return fp, pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    fp = common.pad_dim(common.pad_dim(f, 0, bq), 1, bn)
    return fp, pl.BlockSpec((bq, bn), lambda i, j, k: (i, j))


def _depth_pad(depth: int, merge: str) -> int:
    """Running-best lane width: LANE-aligned, and a power of two on the
    bitonic path (the merge network needs pow2 sequence lengths)."""
    dpad = common.round_up(depth, common.LANE)
    return common.next_pow2(dpad) if merge == "bitonic" else dpad


@functools.partial(
    jax.jit,
    static_argnames=(
        "depth", "mode", "merge", "bq", "bn", "bk", "interpret", "n_docs"
    ),
)
def fused_topk(
    q: jax.Array,  # (B, T)  bf16 / f32 (gemm), int8 (dot), uint32 (lsh)
    docs: jax.Array,  # (N, T) same reduce-axis dtype family as q
    depth: int,
    mode: str = "gemm",
    merge: str = "bitonic",
    bq: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
    filt: jax.Array | None = None,  # (N,) | (B, N) predicate bitmap
    n_docs: int | None = None,  # logical rows; rows >= n_docs never rank
) -> tuple[jax.Array, jax.Array]:
    """Streaming top-``depth`` of q @ docs.T (or LSH collision counts).

    Returns (scores f32 (B, depth), ids int32 (B, depth)), sorted descending
    with ``jax.lax.top_k`` tie semantics; id -1 marks empty (-inf) slots.
    The (B, N) score matrix never exists in HBM.

    ``filt`` (optional): per-doc predicate bitmap, (N,) shared or (B, N)
    per-query; nonzero = keep.  Applied as -inf inside the tile merge, so
    filtered search stays one kernel pass.  ``filt=None`` dispatches the
    exact unfiltered call graph (bitwise identical to not having the arg).

    ``n_docs`` (optional): logical row count when ``docs`` carries tail
    padding beyond the real corpus (the packed segment superbuffer of
    ``core/packed.py`` pads totals to a bucket ladder so executables recur
    across flush/merge cycles).  Rows >= ``n_docs`` ride the exact ragged-N
    mask the kernel already applies, so the padded tail can never rank and
    no bitmap operand is streamed.  Static: shape-stable callers only.
    """
    if interpret is None:
        interpret = common.INTERPRET
    if mode == "lsh":
        # The compare stage materializes a (bq, bn, bk) equality tensor in
        # VMEM — size tiles like ``lsh_match`` (~4 MB), not like the GEMM.
        bq, bn, bk = bq or 16, bn or 128, bk or 512
    else:
        bq, bn, bk = bq or 128, bn or 512, bk or 512
    b, t = q.shape
    n = docs.shape[0]
    if n_docs is None:
        n_docs = n
    assert 0 < n_docs <= n, f"n_docs {n_docs} outside (0, {n}]"
    assert depth <= n_docs, f"depth {depth} > corpus size {n_docs}"
    bq = min(bq, common.round_up(b, 8))
    bn = min(bn, common.round_up(n, common.LANE))
    bk = min(bk, common.round_up(t, common.LANE))
    if mode == "lsh":
        # Distinct fillers so padding never matches (query pad is masked).
        qp = common.pad_dim(common.pad_dim(q, 0, bq), 1, bk, value=LSH_SENTINEL)
        dp = common.pad_dim(
            common.pad_dim(docs, 0, bn), 1, bk, value=np.uint32(LSH_SENTINEL - 1)
        )
        acc_dtype = jnp.int32
    else:
        qp = common.pad_dim(common.pad_dim(q, 0, bq), 1, bk)
        dp = common.pad_dim(common.pad_dim(docs, 0, bn), 1, bk)
        acc_dtype = jnp.int32 if q.dtype in _INT_DTYPES else jnp.float32
    dpad = _depth_pad(depth, merge)
    grid = (qp.shape[0] // bq, dp.shape[0] // bn, qp.shape[1] // bk)
    operands = [qp, dp]
    in_specs = [
        pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
    ]
    if filt is not None:
        fp, f_spec = _filt_operand(filt, bq, bn)
        operands.append(fp)
        in_specs.append(f_spec)

    scores, ids = pl.pallas_call(
        functools.partial(
            _fused_topk_kernel,
            n_j=grid[1], n_k=grid[2], n_docs=n_docs, bn=bn, depth=depth,
            mode=mode, merge=merge, acc_dtype=acc_dtype,
            has_filt=filt is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, dpad), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bq, dpad), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], dpad), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], dpad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((bq, bn), acc_dtype),
            pltpu.MemorySpace.VMEM((bq, dpad), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, dpad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    scores = scores[:b, :depth]
    ids = ids[:b, :depth]
    return scores, jnp.where(scores == -jnp.inf, -1, ids)


def _fused_gathered_kernel(
    q_ref, d_ref, rid_ref, s_ref, i_ref, acc_ref, rs_ref, ri_ref,
    *, n_j: int, n_k: int, n_docs: int, depth: int, mode: str, merge: str,
    acc_dtype,
):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_running():
        rs_ref[...] = jnp.full_like(rs_ref, -jnp.inf)
        ri_ref[...] = jnp.full_like(ri_ref, BIG_ID)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _score_tile(q_ref[0], d_ref[0], mode, acc_dtype)

    @pl.when(k == n_k - 1)
    def _merge():
        tile_s = acc_ref[...].astype(jnp.float32)  # (1, bn)
        # Merge key = GLOBAL doc id: ties then resolve exactly like the dense
        # reference paths (lowest doc id), independent of the block-gather
        # order blockmax stage 1 produced.
        ids = rid_ref[0]
        valid = ids < n_docs  # folds the blockmax padding mask
        tile_s = jnp.where(valid, tile_s, -jnp.inf)
        ids = jnp.where(valid, ids, BIG_ID)
        _merge_if_improves(rs_ref, ri_ref, tile_s, ids, depth, merge,
                           strict=False)

    @pl.when(jnp.logical_and(j == n_j - 1, k == n_k - 1))
    def _flush():
        s_ref[0] = rs_ref[...]
        i_ref[0] = ri_ref[...]


def _gathered_specs(b: int, bn: int, bk: int, dpad: int):
    """Per-query operands carry a unit axis — query (B, 1, T), row ids
    (B, 1, R), results (B, 1, dpad) — so every block's last two dims are
    either (8, 128)-aligned or span the whole dimension, as Mosaic needs."""
    q_spec = pl.BlockSpec((1, 1, bk), lambda i, j, k: (i, 0, k))
    rid_spec = pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, j))
    out_specs = [
        pl.BlockSpec((1, 1, dpad), lambda i, j, k: (i, 0, 0)),
        pl.BlockSpec((1, 1, dpad), lambda i, j, k: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, 1, dpad), jnp.float32),
        jax.ShapeDtypeStruct((b, 1, dpad), jnp.int32),
    ]
    return q_spec, rid_spec, out_specs, out_shape


@functools.partial(
    jax.jit,
    static_argnames=("depth", "n_docs", "mode", "merge", "bn", "bk", "interpret"),
)
def fused_topk_gathered(
    q: jax.Array,  # (B, T)
    docs: jax.Array,  # (B, R, T) per-query gathered candidate rows
    row_ids: jax.Array,  # (B, R) int32 global doc ids; >= n_docs = padding
    depth: int,
    n_docs: int,
    mode: str = "gemm",
    merge: str = "bitonic",
    bn: int = 512,
    bk: int = 512,
    interpret: bool | None = None,
    filt: jax.Array | None = None,  # (B, R) keep-bitmap aligned with row_ids
) -> tuple[jax.Array, jax.Array]:
    """Per-query streaming top-``depth`` over gathered candidate matrices
    (blockmax stage 2: each query scores only its own kept blocks' rows).

    ``mode`` selects the score stage exactly like :func:`fused_topk`: "gemm"
    (bf16/f32/int8 operands) or "lsh" (uint32 signature collision counts).
    Returns (scores f32 (B, depth), ids int32 (B, depth)); id -1 marks
    padded / -inf slots.  Ties break on the lowest GLOBAL doc id, matching
    the dense reference paths.  The (B, R) stage-2 score matrix never exists
    in HBM.

    ``filt`` (optional): (B, R) keep-bitmap aligned with ``row_ids``.  The
    mask folds into the row-id operand (filtered rows take the same
    out-of-range id the in-kernel padding mask drops), so filtering rides
    the existing merge-time mask — still one kernel pass, and ``filt=None``
    leaves the call graph untouched.
    """
    if interpret is None:
        interpret = common.INTERPRET
    b, r, t = docs.shape
    if filt is not None:
        row_ids = jnp.where(filt != 0, row_ids.astype(jnp.int32), BIG_ID)
    assert depth <= r, f"depth {depth} > candidate count {r}"
    bn = min(bn, common.round_up(r, common.LANE))
    bk = min(bk, common.round_up(t, common.LANE))
    if mode == "lsh":
        qp = common.pad_dim(q, 1, bk, value=LSH_SENTINEL)
        dp = common.pad_dim(
            common.pad_dim(docs, 1, bn), 2, bk, value=np.uint32(LSH_SENTINEL - 1)
        )
        acc_dtype = jnp.int32
    else:
        qp = common.pad_dim(q, 1, bk)
        dp = common.pad_dim(common.pad_dim(docs, 1, bn), 2, bk)
        acc_dtype = jnp.int32 if q.dtype in _INT_DTYPES else jnp.float32
    # Padding rows get an out-of-range id so the in-kernel mask drops them.
    rp = common.pad_dim(row_ids.astype(jnp.int32), 1, bn, value=BIG_ID)
    dpad = _depth_pad(depth, merge)
    grid = (b, dp.shape[1] // bn, qp.shape[1] // bk)
    q_spec, rid_spec, out_specs, out_shape = _gathered_specs(b, bn, bk, dpad)

    scores, ids = pl.pallas_call(
        functools.partial(
            _fused_gathered_kernel,
            n_j=grid[1], n_k=grid[2], n_docs=n_docs, depth=depth,
            mode=mode, merge=merge, acc_dtype=acc_dtype,
        ),
        grid=grid,
        in_specs=[
            q_spec,
            pl.BlockSpec((1, bn, bk), lambda i, j, k: (i, j, k)),
            rid_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((1, bn), acc_dtype),
            pltpu.MemorySpace.VMEM((1, dpad), jnp.float32),
            pltpu.MemorySpace.VMEM((1, dpad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qp[:, None, :], dp, rp[:, None, :])
    scores = scores[:, 0, :depth]
    ids = ids[:, 0, :depth]
    return scores, jnp.where(scores == -jnp.inf, -1, ids)


# --------------------------------------------------------------------------
# Quantized-postings variants: dequantization fused into the score stage
# (docs/DESIGN.md §12).  The packed int8/int4 store streams from HBM; tiles
# are unpacked and rescaled in VMEM registers, so the fp32/bf16 posting
# matrix never exists in HBM.
#
#   * bits=8 — the per-doc scale is constant along the reduce axis, so it
#     factorizes out of the dot: the int8 tile is cast to the query dtype
#     (exact: |q| <= 127 is representable in bf16), f32-accumulated across
#     K tiles, and the scale is applied ONCE per (query, doc) at merge time.
#   * bits=4 — nibbles are unpacked and rescaled per group IN REGISTERS
#     before the tile dot, in the canonical ordering of
#     ``common.dequant_int4`` (f32 (nibble-8) * group_scale, one cast to the
#     query dtype), so each dequantized value is bit-identical to the XLA
#     references and the build-time ``dequantize_postings``.  Mosaic has no
#     lane interleave, so the tile is laid out [low nibbles | high nibbles]
#     and the query's columns are permuted to match outside the kernel
#     (:func:`_int4_query`); the scales stream transposed, (groups, docs),
#     so their blocks stay (8, 128)-aligned.
#
# Padding invariants: packed pad byte 0x88 decodes to nibble 8 on both
# halves -> dequantized 0; scale pads are 0 (so any stray nibble still
# dequantizes to 0); query column pads are 0.  Padded doc ROWS are masked
# to (-inf, BIG_ID) by the n_docs check like the fp paths.
# --------------------------------------------------------------------------

INT4_PAD_BYTE = np.uint8(0x88)


def _dequant_tile(d, s, bits: int, group: int, q_dtype):
    """Unpack + rescale one packed doc tile in registers.

    bits=8: (bn, bk) int8 -> q_dtype (scale applied later, post-reduction).
    bits=4: (bn, bk//2) packed + (bk//group, bn) transposed scales ->
    (bn, bk) q_dtype laid out [low nibbles | high nibbles].
    """
    if bits == 8:
        return d.astype(q_dtype)
    d = d.astype(jnp.int32)
    n_groups, bn = s.shape
    per_group = group // 2  # packed bytes per scale group
    s_cols = jnp.broadcast_to(
        s[:, None, :], (n_groups, per_group, bn)
    ).reshape(n_groups * per_group, bn).T  # (bn, bk//2): one scale per byte
    lo = ((d & 0xF).astype(jnp.float32) - 8.0) * s_cols
    hi = ((d >> 4).astype(jnp.float32) - 8.0) * s_cols
    return jnp.concatenate([lo.astype(q_dtype), hi.astype(q_dtype)], axis=1)


def _int4_query(qp, bk: int):
    """Permute a padded query's columns so each ``bk`` block reads [even
    columns | odd columns] — the [low | high] nibble layout of the dequant
    tile (a packed byte holds column 2c low and 2c + 1 high)."""
    b, t = qp.shape
    return qp.reshape(b, t // bk, bk // 2, 2).swapaxes(2, 3).reshape(b, t)


def _fused_topk_quantized_kernel(
    q_ref, d_ref, s_ref, *refs,
    n_j: int, n_k: int, n_docs: int, bn: int, depth: int, merge: str,
    bits: int, group: int, has_filt: bool = False,
):
    if has_filt:
        f_ref, s_out_ref, i_out_ref, acc_ref, rs_ref, ri_ref = refs
    else:
        f_ref = None
        s_out_ref, i_out_ref, acc_ref, rs_ref, ri_ref = refs
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_running():
        rs_ref[...] = jnp.full_like(rs_ref, -jnp.inf)
        ri_ref[...] = jnp.full_like(ri_ref, BIG_ID)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]
    d = _dequant_tile(d_ref[...], s_ref[...], bits, group, q.dtype)
    acc_ref[...] += jnp.dot(q, d.T, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _merge():
        tile_s = acc_ref[...]
        if bits == 8:
            # Per-doc dequant applied once, after the full K reduction.
            tile_s = tile_s * s_ref[...][:, 0][None, :]
        ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, tile_s.shape, 1)
        valid = ids < n_docs
        if has_filt:
            valid = valid & (f_ref[...] != 0)
        tile_s = jnp.where(valid, tile_s, -jnp.inf)
        ids = jnp.where(valid, ids, BIG_ID)
        _merge_if_improves(rs_ref, ri_ref, tile_s, ids, depth, merge,
                           strict=True)

    @pl.when(jnp.logical_and(j == n_j - 1, k == n_k - 1))
    def _flush():
        s_out_ref[...] = rs_ref[...]
        i_out_ref[...] = ri_ref[...]


def _quantized_operands(q, docs, scale, bits, group, bq, bn, bk):
    """Pad the query / packed store / scales to tile multiples.

    Returns (qp, dp, sp) plus the padded logical column count.  For int4 the
    query pads to the PACKED width (2 * packed cols per bk block); packed
    pads with 0x88 and scales with 0 so padding always dequantizes to 0."""
    qp = common.pad_dim(common.pad_dim(q, 0, bq), 1, bk)
    if bits == 8:
        dp = common.pad_dim(common.pad_dim(docs, 0, bn), 1, bk)
        sp = common.pad_dim(scale, 0, bn)  # (N', 1) f32
        assert dp.shape[1] == qp.shape[1], (dp.shape, qp.shape)
        return qp, dp, sp
    dp = common.pad_dim(
        common.pad_dim(docs, 0, bn, value=INT4_PAD_BYTE),
        1, bk // 2, value=INT4_PAD_BYTE,
    )
    sp = common.pad_dim(common.pad_dim(scale, 0, bn), 1, bk // group)
    assert 2 * dp.shape[1] == qp.shape[1], (dp.shape, qp.shape)
    assert sp.shape[1] * group == qp.shape[1], (sp.shape, qp.shape)
    return _int4_query(qp, bk), dp, jnp.swapaxes(sp, 0, 1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "depth", "bits", "group", "merge", "bq", "bn", "bk", "interpret",
        "n_docs",
    ),
)
def fused_topk_quantized(
    q: jax.Array,  # (B, T) bf16 / f32 query operand
    docs: jax.Array,  # (N, T) int8 | (N, Tg/2) uint8 packed nibbles
    scale: jax.Array,  # (N, 1) | (N, Tg/group) f32 dequant scales
    depth: int,
    bits: int = 8,
    group: int = 0,
    merge: str = "bitonic",
    bq: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
    filt: jax.Array | None = None,  # (N,) | (B, N) predicate bitmap
    n_docs: int | None = None,  # logical rows; rows >= n_docs never rank
) -> tuple[jax.Array, jax.Array]:
    """Streaming top-``depth`` of q @ dequant(docs, scale).T with the
    dequantization fused into the score stage — only the packed store and
    the scales ever stream from HBM.  Same output contract (and ``filt`` /
    ``n_docs`` semantics) as :func:`fused_topk`."""
    if interpret is None:
        interpret = common.INTERPRET
    bq, bn, bk = bq or 128, bn or 512, bk or 512
    b, t = q.shape
    n = docs.shape[0]
    if n_docs is None:
        n_docs = n
    assert 0 < n_docs <= n, f"n_docs {n_docs} outside (0, {n}]"
    assert depth <= n_docs, f"depth {depth} > corpus size {n_docs}"
    bq = min(bq, common.round_up(b, 8))
    bn = min(bn, common.round_up(n, common.LANE))
    bk = min(bk, common.round_up(t, common.LANE))
    if bits == 4:
        assert group and bk % group == 0, (
            f"doc-tile reduce width {bk} must be a multiple of the int4 "
            f"scale group {group}"
        )
    qp, dp, sp = _quantized_operands(q, docs, scale, bits, group, bq, bn, bk)
    dpad = _depth_pad(depth, merge)
    grid = (qp.shape[0] // bq, dp.shape[0] // bn, qp.shape[1] // bk)

    if bits == 8:
        d_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
        s_spec = pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0))
    else:
        d_spec = pl.BlockSpec((bn, bk // 2), lambda i, j, k: (j, k))
        s_spec = pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j))
    operands = [qp, dp, sp]
    in_specs = [
        pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
        d_spec,
        s_spec,
    ]
    if filt is not None:
        fp, f_spec = _filt_operand(filt, bq, bn)
        operands.append(fp)
        in_specs.append(f_spec)

    scores, ids = pl.pallas_call(
        functools.partial(
            _fused_topk_quantized_kernel,
            n_j=grid[1], n_k=grid[2], n_docs=n_docs, bn=bn, depth=depth,
            merge=merge, bits=bits, group=group, has_filt=filt is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, dpad), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bq, dpad), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], dpad), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], dpad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((bq, bn), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, dpad), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, dpad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    scores = scores[:b, :depth]
    ids = ids[:b, :depth]
    return scores, jnp.where(scores == -jnp.inf, -1, ids)


def _fused_gathered_quantized_kernel(
    q_ref, d_ref, s_ref, rid_ref, s_out_ref, i_out_ref, acc_ref, rs_ref,
    ri_ref, *, n_j: int, n_k: int, n_docs: int, depth: int, merge: str,
    bits: int, group: int,
):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_running():
        rs_ref[...] = jnp.full_like(rs_ref, -jnp.inf)
        ri_ref[...] = jnp.full_like(ri_ref, BIG_ID)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    d = _dequant_tile(d_ref[0], s_ref[0], bits, group, q.dtype)
    acc_ref[...] += jnp.dot(q, d.T, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _merge():
        tile_s = acc_ref[...]  # (1, bn)
        if bits == 8:
            tile_s = tile_s * s_ref[0][:, 0][None, :]
        ids = rid_ref[0]
        valid = ids < n_docs
        tile_s = jnp.where(valid, tile_s, -jnp.inf)
        ids = jnp.where(valid, ids, BIG_ID)
        _merge_if_improves(rs_ref, ri_ref, tile_s, ids, depth, merge,
                           strict=False)

    @pl.when(jnp.logical_and(j == n_j - 1, k == n_k - 1))
    def _flush():
        s_out_ref[0] = rs_ref[...]
        i_out_ref[0] = ri_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "depth", "n_docs", "bits", "group", "merge", "bn", "bk", "interpret"
    ),
)
def fused_topk_gathered_quantized(
    q: jax.Array,  # (B, T)
    docs: jax.Array,  # (B, R, T) int8 | (B, R, Tg/2) packed candidate rows
    scale: jax.Array,  # (B, R, 1) | (B, R, Tg/group) f32 scales
    row_ids: jax.Array,  # (B, R) int32 global doc ids; >= n_docs = padding
    depth: int,
    n_docs: int,
    bits: int = 8,
    group: int = 0,
    merge: str = "bitonic",
    bn: int = 512,
    bk: int = 512,
    interpret: bool | None = None,
    filt: jax.Array | None = None,  # (B, R) keep-bitmap aligned with row_ids
) -> tuple[jax.Array, jax.Array]:
    """Quantized-store variant of :func:`fused_topk_gathered` (blockmax
    stage 2): per-query gathered packed rows + scales are dequantized in
    registers and streamed through the same running top-``depth`` merge on
    GLOBAL doc ids.  ``filt`` folds into the row-id operand exactly like
    :func:`fused_topk_gathered`."""
    if interpret is None:
        interpret = common.INTERPRET
    b, r, tc = docs.shape
    if filt is not None:
        row_ids = jnp.where(filt != 0, row_ids.astype(jnp.int32), BIG_ID)
    t = q.shape[1]
    assert depth <= r, f"depth {depth} > candidate count {r}"
    bn = min(bn, common.round_up(r, common.LANE))
    bk = min(bk, common.round_up(t, common.LANE))
    if bits == 4:
        assert group and bk % group == 0, (
            f"doc-tile reduce width {bk} must be a multiple of the int4 "
            f"scale group {group}"
        )
    qp = common.pad_dim(q, 1, bk)
    if bits == 8:
        dp = common.pad_dim(common.pad_dim(docs, 1, bn), 2, bk)
        sp = common.pad_dim(scale, 1, bn)
        d_spec = pl.BlockSpec((1, bn, bk), lambda i, j, k: (i, j, k))
        s_spec = pl.BlockSpec((1, bn, 1), lambda i, j, k: (i, j, 0))
    else:
        dp = common.pad_dim(
            common.pad_dim(docs, 1, bn, value=INT4_PAD_BYTE),
            2, bk // 2, value=INT4_PAD_BYTE,
        )
        sp = jnp.swapaxes(
            common.pad_dim(common.pad_dim(scale, 1, bn), 2, bk // group), 1, 2
        )
        assert 2 * dp.shape[2] == qp.shape[1], (dp.shape, qp.shape)
        qp = _int4_query(qp, bk)
        d_spec = pl.BlockSpec((1, bn, bk // 2), lambda i, j, k: (i, j, k))
        s_spec = pl.BlockSpec((1, bk // group, bn), lambda i, j, k: (i, k, j))
    rp = common.pad_dim(row_ids.astype(jnp.int32), 1, bn, value=BIG_ID)
    dpad = _depth_pad(depth, merge)
    grid = (b, dp.shape[1] // bn, qp.shape[1] // bk)
    q_spec, rid_spec, out_specs, out_shape = _gathered_specs(b, bn, bk, dpad)

    scores, ids = pl.pallas_call(
        functools.partial(
            _fused_gathered_quantized_kernel,
            n_j=grid[1], n_k=grid[2], n_docs=n_docs, depth=depth,
            merge=merge, bits=bits, group=group,
        ),
        grid=grid,
        in_specs=[q_spec, d_spec, s_spec, rid_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((1, bn), jnp.float32),
            pltpu.MemorySpace.VMEM((1, dpad), jnp.float32),
            pltpu.MemorySpace.VMEM((1, dpad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qp[:, None, :], dp, sp, rp[:, None, :])
    scores = scores[:, 0, :depth]
    ids = ids[:, 0, :depth]
    return scores, jnp.where(scores == -jnp.inf, -1, ids)
