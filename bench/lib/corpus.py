"""Seeded corpus and query pool, made on the device in one jitted call.

The statistics are those of ``src/repro/data/embeddings.py``
(``make_corpus`` / ``GLOVE_LIKE``), copied here so that the yardstick cannot
move with the program: a power-law spectrum sigma_i ~ i^-alpha (rescaled to
unit mean square) rotated by a random orthogonal matrix, a common mean
component of norm ``mean_strength``, and heavy-tailed per-row norms drawn
from a Pareto(``pareto``) law with minimum 1 (NumPy's ``pareto(a) + 1``).
The draws come from ``jax.random`` rather than NumPy, so the rows differ
from the program's generator while the statistics match.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones above 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "dim", "alpha", "mean_strength", "pareto"))
def _make(key, n: int, dim: int, alpha: float, mean_strength: float, pareto: float):
    kz, kq, km, ks = jax.random.split(key, 4)
    z = jax.random.normal(kz, (n, dim), jnp.float32)
    s = jnp.arange(1, dim + 1, dtype=jnp.float32) ** (-alpha)
    s = s / jnp.sqrt(jnp.mean(s**2))
    q, _ = jnp.linalg.qr(jax.random.normal(kq, (dim, dim), jnp.float32))
    x = jnp.dot(z * s[None, :], q, precision=jax.lax.Precision.HIGHEST)
    mu = jax.random.normal(km, (dim,), jnp.float32)
    x = x + (mu / jnp.linalg.norm(mu) * mean_strength)[None, :]
    scale = jax.random.pareto(ks, pareto, (n,), jnp.float32)
    return x * scale[:, None]


def make_corpus(seed: int, spec: Dict[str, Any]) -> jax.Array:
    """(n_docs, dim) float32 rows on the default device."""
    return _make(
        key_from_seed(seed), int(spec["n_docs"]), int(spec["dim"]),
        float(spec["alpha"]), float(spec["mean_strength"]), float(spec["pareto"]),
    )


def pool_rows(seed: int, n_docs: int, pool: int) -> np.ndarray:
    """Row ids of the query pool: distinct corpus rows drawn from the seed
    (the paper's word-similarity setup, where queries are corpus words)."""
    rng = np.random.default_rng([seed, 1])
    return np.sort(rng.choice(n_docs, size=pool, replace=False))
