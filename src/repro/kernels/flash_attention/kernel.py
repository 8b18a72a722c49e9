"""Pallas TPU kernel: causal GQA flash attention (prefill hot path).

Online-softmax tiling (Dao et al., adapted to TPU VMEM/MXU): the query tile
(bq x d) stays resident; key/value tiles stream through VMEM; running
(max, sum, acc) statistics live in f32 scratch carried across the innermost
KV grid axis.  Causality is exploited structurally: KV tiles strictly above
the diagonal are skipped with ``pl.when`` (no wasted MXU work), and the
intra-tile diagonal is masked.

GQA: query head h reads KV head h // group via the K/V BlockSpec index maps -
no KV replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, bq, bk, n_k, scale, true_len
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal structure: KV tile fully above the diagonal contributes nothing.
    needed = ki * bk <= qi * bq + bq - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (q_pos >= k_pos) & (k_pos < true_len)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 1) broadcast over lanes
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal GQA flash attention.  S is padded to the tile size internally;
    D should be MXU-friendly (it is 128 for every assigned arch)."""
    if interpret is None:
        interpret = common.INTERPRET
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = 1.0 / (d**0.5)
    bq = min(bq, common.round_up(s, 8))
    bk = min(bk, common.round_up(s, common.LANE))
    qp = common.pad_dim(q, 2, bq)
    kp = common.pad_dim(k, 2, bk)
    vp = common.pad_dim(v, 2, bk)
    n_q, n_k = qp.shape[2] // bq, kp.shape[2] // bk
    grid = (b, hq, n_q, n_k)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            bq=bq,
            bk=bk,
            n_k=n_k,
            scale=scale,
            true_len=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((bq, d), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, 1), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :s, :]
