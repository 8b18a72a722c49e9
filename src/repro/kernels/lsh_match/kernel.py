"""Pallas TPU kernel: MinHash signature collision counting.

scores[i,j] = #{s : sig_q[i,s] == sig_d[j,s] != SENTINEL} - the lexical-LSH
match score.  Integer equality + popcount-style reduce: a VPU workload with
no MXU use (docs/DESIGN.md §10).  The signature axis is tiled through the grid so
the (bq, bn, bs) broadcast-compare stays inside VMEM; partial counts
accumulate in an int32 scratch across signature tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

SENTINEL = np.uint32(0xFFFFFFFF)


def _lsh_kernel(q_ref, d_ref, o_ref, acc_ref, *, n_s: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # (bq, bs) uint32
    d = d_ref[...]  # (bn, bs) uint32
    eq = (q[:, None, :] == d[None, :, :]) & (q[:, None, :] != SENTINEL)
    acc_ref[...] += jnp.sum(eq.astype(jnp.int32), axis=-1)

    @pl.when(s == n_s - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bq", "bn", "bs", "interpret"))
def lsh_match_scores(
    sig_q: jax.Array,  # (B, S) uint32
    sig_d: jax.Array,  # (N, S) uint32
    bq: int = 16,
    bn: int = 128,
    bs: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = common.INTERPRET
    b, s = sig_q.shape
    n = sig_d.shape[0]
    bq = min(bq, common.round_up(b, 8))
    bn = min(bn, common.round_up(n, 8))
    bs = min(bs, common.round_up(s, common.LANE))
    # Pad signature axis with DISTINCT fillers so padding never matches:
    # queries get SENTINEL (masked), docs get SENTINEL-1.
    qp = common.pad_dim(common.pad_dim(sig_q, 0, bq), 1, bs, value=SENTINEL)
    dp = common.pad_dim(
        common.pad_dim(sig_d, 0, bn), 1, bs, value=np.uint32(SENTINEL - 1)
    )
    grid = (qp.shape[0] // bq, dp.shape[0] // bn, qp.shape[1] // bs)

    out = pl.pallas_call(
        functools.partial(_lsh_kernel, n_s=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bs), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bs), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], dp.shape[0]), jnp.int32),
        scratch_shapes=[pltpu.MemorySpace.VMEM((bq, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, dp)
    return out[:b, :n]
