"""Plain reference of lexical LSH matching (arXiv:1910.10208 §2; Lucene's
MinHashFilter), written from the definitions and importing nothing of the
system under test.

  * a row x is unit-normalized; feature i with value rounded to ``decimals``
    places, code c_i = round(x_i * 10^decimals), is the token
    mix32(i * G + (c_i + 2^16)) with G = 0x9E3779B9 (the integer carrier of
    the string ``i_c``); n-grams are not covered (ngram = 1);
  * hash function k has seed s_k = mix32(k * G + seed) for k = 1..h; token t
    hashes to v = mix32(t xor s_k), lands in bucket v mod b, and each bucket
    keeps its least v; an empty bucket holds 0xFFFFFFFF and never matches;
  * score(q, d) = number of signature slots where q and d hold the same
    value, empty slots of q excluded.  Scores are exact integers.

mix32 is the splitmix32 finalizer: x ^= x >> 16; x *= 0x7FEB352D;
x ^= x >> 15; x *= 0x846CA68B; x ^= x >> 16 (all mod 2^32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
EMPTY = np.uint32(0xFFFFFFFF)
SCORE_TOL = 0.0  # integer counts compare exactly


def mix32(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def normalize(x: jax.Array) -> jax.Array:
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), 1e-12)


def prepare(corpus: jax.Array, args: dict, block: int) -> dict:
    if int(args.get("ngram", 1)) != 1:
        raise ValueError("this reference covers ngram = 1")
    h = int(args.get("hashes", 1))
    seed = int(args.get("seed", 0x5EED))
    seeds = mix32(jnp.arange(1, h + 1, dtype=jnp.uint32) * GOLDEN + np.uint32(seed & 0xFFFFFFFF))
    return {
        "buckets": int(args.get("buckets", 300)), "seeds": seeds,
        "scale": float(10 ** int(args.get("decimals", 1))),
    }


def _signatures(state: dict, x_norm: jax.Array) -> jax.Array:
    n, m = x_norm.shape
    codes = jnp.round(x_norm * state["scale"]).astype(jnp.int32)
    ucodes = (codes + jnp.int32(1 << 16)).astype(jnp.uint32)
    toks = mix32(jnp.arange(m, dtype=jnp.uint32) * GOLDEN + ucodes)
    b = state["buckets"]
    rows = jnp.arange(n)[:, None]
    sigs = []
    for k in range(state["seeds"].shape[0]):
        v = mix32(toks ^ state["seeds"][k])
        sig = jnp.full((n, b), EMPTY, jnp.uint32).at[rows, (v % np.uint32(b)).astype(jnp.int32)].min(v)
        sigs.append(sig)
    return jnp.concatenate(sigs, axis=-1)


def encode_queries(state: dict, q_norm: jax.Array) -> jax.Array:
    return _signatures(state, q_norm)


def encode_docs(state: dict, x: jax.Array) -> jax.Array:
    return _signatures(state, normalize(x))


def scores(q_rep: jax.Array, d_rep: jax.Array) -> jax.Array:
    """(S, T) x (n, T) -> (S, n) collision counts, as float32."""
    hit = (q_rep[:, None, :] == d_rep[None, :, :]) & (q_rep[:, None, :] != EMPTY)
    return jnp.sum(hit, axis=-1, dtype=jnp.int32).astype(jnp.float32)


def pair_scores(q_rep: jax.Array, d_rep: jax.Array) -> jax.Array:
    """(S, T) x (S, m, T) -> (S, m)."""
    hit = (q_rep[:, None, :] == d_rep) & (q_rep[:, None, :] != EMPTY)
    return jnp.sum(hit, axis=-1, dtype=jnp.int32).astype(jnp.float32)


def lower_postings(d_rep: jax.Array) -> jax.Array:
    """Signatures are exact hashes, with no lower precision: the control
    lowers only the rerank."""
    return d_rep
