"""Plain reference of fake-words matching under Lucene's ClassicSimilarity.

From the paper (arXiv:1910.10208 §2, after Amato et al. 2016) and Lucene's
ClassicSimilarity, written from the definitions and importing nothing of
the system under test:

  * a row x is unit-normalized; feature i becomes term i with frequency
    round(Q * max(x_i, 0)) and term m + i with round(Q * max(-x_i, 0));
  * df(t) = rows holding term t; idf(t) = 1 + ln(N / (df(t) + 1));
    norm(d) = 1 / sqrt(max(sum_t tf_d(t), 1));
  * score(q, d) = sum_t tf_q(t) * w(d, t), where the stored posting weight
    w(d, t) = sqrt(tf_d(t)) * idf(t)^2 * norm(d) is held in bfloat16, the
    postings precision the configuration states.  Sums are float32 at full
    matmul precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# Relative slack on match scores (of the best score).  A row normalized one
# ulp apart can round one term frequency the other way at a .5 boundary,
# which moves that row's score by up to ~0.3%; float32 sums taken in
# another order move it by ~1e-6.
SCORE_TOL = 3e-3


def normalize(x: jax.Array) -> jax.Array:
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), 1e-12)


def term_freqs(x_norm: jax.Array, q: int) -> jax.Array:
    qf = jnp.float32(q)
    return jnp.concatenate(
        [jnp.round(qf * jnp.maximum(x_norm, 0.0)), jnp.round(qf * jnp.maximum(-x_norm, 0.0))],
        axis=-1,
    )


def prepare(corpus: jax.Array, args: dict, block: int) -> dict:
    """Collection statistics over the whole corpus, in blocks of rows."""
    q = int(args["quantization"])
    if args.get("scoring", "classic") != "classic" or float(args.get("df_max_ratio", 1.0)) < 1.0:
        raise ValueError("this reference covers classic scoring without df pruning")
    n = corpus.shape[0]

    @jax.jit
    def df_block(x):
        return jnp.sum(term_freqs(normalize(x), q) > 0, axis=0).astype(jnp.int32)

    df = sum(df_block(corpus[i : i + block]) for i in range(0, n, block))
    idf = 1.0 + jnp.log(jnp.float32(n) / (df.astype(jnp.float32) + 1.0))
    return {"q": q, "idf2": idf * idf}


def encode_queries(state: dict, q_norm: jax.Array) -> jax.Array:
    return term_freqs(q_norm, state["q"])


def encode_docs(state: dict, x: jax.Array) -> jax.Array:
    """Posting weights w(d, t) of raw rows ``x``, bf16-rounded, as float32."""
    tf = term_freqs(normalize(x), state["q"])
    norm = jax.lax.rsqrt(jnp.maximum(jnp.sum(tf, axis=-1, keepdims=True), 1.0))
    w = jnp.sqrt(tf) * state["idf2"][None, :] * norm
    # Rounded to bfloat16 explicitly: XLA may drop an f32 -> bf16 -> f32
    # round trip as excess precision, reduce_precision it may not.
    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def scores(q_rep: jax.Array, d_rep: jax.Array) -> jax.Array:
    """(S, T) x (n, T) -> (S, n) match scores."""
    return jnp.dot(q_rep, d_rep.T, precision=HIGHEST)


def pair_scores(q_rep: jax.Array, d_rep: jax.Array) -> jax.Array:
    """(S, T) x (S, m, T) -> (S, m): each query against its own rows."""
    return jnp.einsum("st,smt->sm", q_rep, d_rep, precision=HIGHEST)


def lower_postings(d_rep: jax.Array) -> jax.Array:
    """The precision control's postings: int8 with a per-row scale
    (max |w| / 127), one step below the stated bfloat16."""
    scale = jnp.maximum(jnp.max(jnp.abs(d_rep), axis=-1, keepdims=True), 1e-12) / 127.0
    return jnp.round(d_rep / scale) * scale
