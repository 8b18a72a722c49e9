"""Pallas TPU kernel: fake-words index-scan GEMM.

The inverted-index scoring loop of the paper's fake-words method, realized as
a tiled GEMM over the stored term-frequency matrix (docs/DESIGN.md §3):

  * classic mode - scores = q_tf @ scored.T where ``scored`` already folds
    sqrt(tf_d) * idf^2 * norm_d (bf16 operands, f32 accumulate on the MXU);
  * dot mode    - scores = q_lift @ tf.T with int8 operands and int32
    accumulate (the MXU's 4x-throughput integer path); q_lift = [u; -u],
    u = q+ - q-.

Grid = (query tiles, doc tiles, dim tiles); the dim (K) axis is innermost and
marked "arbitrary" so the accumulator scratch carries across K steps.  Doc
blocks stream HBM->VMEM once per query tile: the op is memory-bound at
production corpus sizes, which is why the df-pruning / blockmax levers in
core/ matter (they cut streamed bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _score_kernel(q_ref, d_ref, o_ref, acc_ref, *, n_k: int, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        q_ref[...], d_ref[...].T, preferred_element_type=acc_dtype
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bn", "bk", "out_dtype", "interpret")
)
def score_matmul(
    q: jax.Array,  # (B, T)  bf16 (classic) or int8 (dot)
    docs: jax.Array,  # (N, T)  bf16 (classic) or int8 (dot)
    bq: int = 128,
    bn: int = 512,
    bk: int = 512,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """Tiled scores = q @ docs.T with MXU-aligned VMEM blocks."""
    if interpret is None:
        interpret = common.INTERPRET
    b, t = q.shape
    n = docs.shape[0]
    bq = min(bq, common.round_up(b, 8))
    bn = min(bn, common.round_up(n, common.LANE))
    bk = min(bk, common.round_up(t, common.LANE))
    qp = common.pad_dim(common.pad_dim(q, 0, bq), 1, bk)
    dp = common.pad_dim(common.pad_dim(docs, 0, bn), 1, bk)
    acc_dtype = jnp.int32 if q.dtype in (jnp.int8, jnp.int32) else jnp.float32
    grid = (qp.shape[0] // bq, dp.shape[0] // bn, qp.shape[1] // bk)

    out = pl.pallas_call(
        functools.partial(_score_kernel, n_k=grid[2], acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], dp.shape[0]), out_dtype),
        scratch_shapes=[pltpu.MemorySpace.VMEM((bq, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, dp)
    return out[:b, :n]
