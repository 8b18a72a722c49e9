"""Pallas TPU kernel: cosine scoring GEMM with fused normalization epilogue.

scores = (q @ docs.T) * inv_norm_d  - the exact-rerank / brute-force /
``retrieval_cand`` hot path.  Queries are pre-normalized (cheap, B rows);
document norms fold into the epilogue so the docs matrix streams HBM->VMEM
once, unmodified (no materialized normalized copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _cosine_kernel(q_ref, d_ref, inv_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        q_ref[...], d_ref[...].T, preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * inv_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "bk", "interpret"))
def cosine_scores(
    q: jax.Array,  # (B, dim), unit-normalized
    docs: jax.Array,  # (N, dim), raw
    inv_norm: jax.Array,  # (N,) 1/||doc||
    bq: int = 128,
    bn: int = 512,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = common.INTERPRET
    b, dim = q.shape
    n = docs.shape[0]
    bq = min(bq, common.round_up(b, 8))
    bn = min(bn, common.round_up(n, common.LANE))
    bk = min(bk, common.round_up(dim, common.LANE))
    qp = common.pad_dim(common.pad_dim(q, 0, bq), 1, bk)
    dp = common.pad_dim(common.pad_dim(docs, 0, bn), 1, bk)
    ip = common.pad_dim(inv_norm[None, :], 1, bn)  # (1, N_pad)
    grid = (qp.shape[0] // bq, dp.shape[0] // bn, qp.shape[1] // bk)

    out = pl.pallas_call(
        functools.partial(_cosine_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], dp.shape[0]), jnp.float32),
        scratch_shapes=[pltpu.MemorySpace.VMEM((bq, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, dp, ip)
    return out[:b, :n]
