"""Public wrappers routing the search hot paths onto the fused top-k kernel.

Each wrapper prepares the query operand exactly like its ``core/`` reference
path (df-prune keep-mask folded into the query tile, [u; -u] int8 lift for
dot mode, unit-normalization for cosine) and then streams the stored index
through :func:`repro.kernels.fused_topk.kernel.fused_topk` — the (B, N)
score matrix never materializes.  ``repro.core`` imports these lazily to
avoid an import cycle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.fused_topk.kernel import (
    fused_topk,
    fused_topk_gathered,
    fused_topk_gathered_quantized,
    fused_topk_quantized,
    lane_align,
    pad_query,
)

__all__ = [
    "resolve_use_kernel",
    "gather_filt",
    "classic_topk",
    "dot_topk",
    "cosine_topk",
    "lsh_topk",
    "scan_l2_topk",
    "fused_topk",
    "fused_topk_gathered",
    "fused_topk_quantized",
    "fused_topk_gathered_quantized",
    "postings_topk",
    "postings_topk_gathered",
    "lane_align",
    "pad_query",
]


def resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    """None -> fused Pallas path on TPU, XLA reference path elsewhere."""
    return common.USE_KERNEL_DEFAULT if use_kernel is None else use_kernel


def gather_filt(
    filt: Optional[jax.Array], row_ids: jax.Array, n_docs: int
) -> Optional[jax.Array]:
    """Gather a per-doc predicate bitmap ((N,) shared or (B, N) per-query)
    into the (B, R) row-aligned keep-bitmap the gathered kernels / refs
    take.  Out-of-range padding rows gather doc 0's bit but stay masked by
    the kernels' own ``row_ids < n_docs`` check."""
    if filt is None:
        return None
    safe = jnp.minimum(row_ids, n_docs - 1)
    if filt.ndim == 1:
        return filt[safe]
    return jnp.take_along_axis(filt, safe, axis=1)


def classic_topk(
    index, q_tf: jax.Array, depth: int, df_max_ratio: float = 1.0,
    interpret: bool | None = None, filt: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused ClassicSimilarity top-depth over a FakeWordsIndex (bf16 GEMM
    against the precomputed ``scored`` matrix, keep-mask folded into q)."""
    from repro.core import fakewords

    qv = fakewords.classic_query(index, q_tf, df_max_ratio)
    return fused_topk(qv, index.scored, depth, interpret=interpret, filt=filt)


def dot_topk(
    index, q_tf: jax.Array, depth: int, df_max_ratio: float = 1.0,
    interpret: bool | None = None, filt: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused integer-dot top-depth (int8 MXU path, [u; -u] query lift)."""
    from repro.core import fakewords

    qv = fakewords.dot_query(index, q_tf, df_max_ratio, dtype=jnp.int8)
    return fused_topk(qv, index.tf, depth, interpret=interpret, filt=filt)


def cosine_topk(
    corpus: jax.Array, queries: jax.Array, depth: int,
    interpret: bool | None = None, filt: jax.Array | None = None,
    n_docs: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused exact-cosine top-depth (operands must be unit-normalized)."""
    return fused_topk(
        queries, corpus, depth, interpret=interpret, filt=filt, n_docs=n_docs
    )


def lsh_topk(
    sig_q: jax.Array, sig_d: jax.Array, depth: int,
    interpret: bool | None = None, filt: jax.Array | None = None,
    n_docs: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused MinHash collision-count top-depth (VPU compare+reduce stage)."""
    return fused_topk(
        sig_q, sig_d, depth, mode="lsh", interpret=interpret, filt=filt,
        n_docs=n_docs,
    )


def postings_topk(
    pq, qv: jax.Array, depth: int, interpret: bool | None = None,
    filt: jax.Array | None = None, n_docs: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused top-depth over a packed :class:`repro.core.types.
    QuantizedPostings` store — dequantization happens in VMEM registers
    (docs/DESIGN.md §12).  ``qv`` is the mode's float query operand."""
    return fused_topk_quantized(
        qv, pq.q, pq.scale, depth, bits=pq.bits, group=pq.group,
        interpret=interpret, filt=filt, n_docs=n_docs,
    )


def postings_topk_gathered(
    pq, qv: jax.Array, row_ids: jax.Array, depth: int, n_docs: int,
    interpret: bool | None = None, filt: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused gathered-candidates top-depth over packed rows of a
    :class:`repro.core.types.QuantizedPostings` store (blockmax stage 2).
    Gathers the packed rows + scales here so callers stay one-liners.
    ``filt`` is per-doc ((N,) | (B, N)); it gathers alongside the rows."""
    import jax.numpy as jnp

    safe = jnp.minimum(row_ids, pq.num_docs - 1)
    return fused_topk_gathered_quantized(
        qv, pq.q[safe], pq.scale[safe], row_ids, depth, n_docs,
        bits=pq.bits, group=pq.group, interpret=interpret,
        filt=gather_filt(filt, row_ids, n_docs),
    )


def lift_l2(points: jax.Array) -> jax.Array:
    """``[d; -||d||^2]`` doc-side lift for :func:`scan_l2_topk`.  Precompute
    at index build time — lifting per search would re-materialize a full
    index copy on a path whose point is cutting HBM traffic."""
    d2 = jnp.sum(points * points, axis=-1)  # (N,)
    return jnp.concatenate([points, -d2[:, None]], axis=-1)


def scan_l2_topk(
    lifted: jax.Array, q_reduced: jax.Array, depth: int,
    interpret: bool | None = None, filt: jax.Array | None = None,
    n_docs: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused exact reduced-space L2 top-depth (kd-tree scan backend).

    -||q - d||^2 + ||q||^2 = 2 q.d - ||d||^2 is a plain GEMM after the lift
    q' = [2q; 1], d' = [d; -||d||^2] (``lifted``, from :func:`lift_l2`), so
    the negated-squared-distance scores stream through the fused kernel and
    the (B, N) matrix never hits HBM."""
    qa = jnp.concatenate(
        [2.0 * q_reduced, jnp.ones((q_reduced.shape[0], 1), q_reduced.dtype)],
        axis=-1,
    )
    return fused_topk(
        qa, lifted, depth, interpret=interpret, filt=filt, n_docs=n_docs
    )
