"""The comparison that decides ``correct``, and recall against exact search.

Each answer of the window (a top-k list of ids with their scores) is judged
by what it says, against the configuration's plain reference
(``bench/references/<reference>.py``) run over the same corpus:

  * ``invalid``: answers that are short or hold an id out of range or
    twice (the harness adds requests whose answer never came).  Exact; its
    limit is 0.
  * ``match_gap``: the widest shortfall of a returned id's reference match
    score below the reference's ``depth``-th best score, over the best score.
    A returned id has to be one the match stage could select.
  * ``rerank_gap``: the widest gap by which the exact cosine of a candidate
    that the match stage must have kept (reference score above the
    ``depth``-th best, by more than rounding) beats the worst returned id.
    The rerank has to keep the best of its candidates.
  * ``score_err``: the widest distance between a returned score and the
    exact cosine of its id, in float64.

Candidates tied at the ``depth``-th score may be kept or dropped by either
side, so neither gap counts them against an answer.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from types import ModuleType
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

@dataclasses.dataclass
class Answers:
    """The window's answers: per request, its pool index, ids and scores
    (``None`` where no answer came)."""

    picks: np.ndarray
    ids: List[Optional[np.ndarray]]
    scores: List[Optional[np.ndarray]]


class Streamer:
    """A reference module's jitted, block-streaming view of one corpus.

    The corpus is zero-padded to whole blocks once; each block is read with
    a dynamic slice, so every step compiles once per process and hits the
    persistent compilation cache in the next.  Arrays of the reference's
    state go in as arguments, never as constants baked into a program."""

    def __init__(self, ref: ModuleType, state: dict, corpus: jax.Array, block: int):
        self.ref, self.n, self.block = ref, int(corpus.shape[0]), block
        self.arrays = {k: v for k, v in state.items() if isinstance(v, jax.Array)}
        static = {k: v for k, v in state.items() if k not in self.arrays}
        pad = (-self.n) % block
        self.corpus = jnp.pad(corpus, ((0, pad), (0, 0))) if pad else corpus

        def encode(arrays, c, start, lower):
            d = ref.encode_docs({**static, **arrays}, jax.lax.dynamic_slice_in_dim(c, start, block))
            return ref.lower_postings(d) if lower else d

        def merge(carry_v, carry_i, q_rep, d_rep, start, n):
            s = ref.scores(q_rep, d_rep)
            ids = start + jnp.arange(block, dtype=jnp.int32)[None, :]
            s = jnp.where(ids < n, s, -jnp.inf)
            return _merge_top(carry_v, carry_i, s, jnp.broadcast_to(ids, s.shape))

        def pairs(arrays, c, q_rep, ids):
            d = ref.encode_docs({**static, **arrays}, c[ids.reshape(-1)])
            return ref.pair_scores(q_rep, d.reshape(ids.shape + (-1,)))

        self._encode = jax.jit(encode, static_argnums=3)
        self._merge = jax.jit(merge)
        self._pairs = jax.jit(pairs)

    def topd(self, q_rep: jax.Array, depth: int, q_chunk: int, lower: bool = False):
        """Top-``depth`` reference match scores and ids of each query over
        the whole corpus; each block is encoded once for all queries."""
        chunks = [q_rep[c : c + q_chunk] for c in range(0, q_rep.shape[0], q_chunk)]
        carry = [(jnp.full((len(q), depth), -jnp.inf, jnp.float32),
                  jnp.full((len(q), depth), -1, jnp.int32)) for q in chunks]
        n = jnp.int32(self.n)
        for start in range(0, self.corpus.shape[0], self.block):
            st = jnp.int32(start)
            d_rep = self._encode(self.arrays, self.corpus, st, lower)
            carry = [self._merge(v, i, q, d_rep, st, n) for (v, i), q in zip(carry, chunks)]
        return (np.concatenate([np.asarray(v) for v, _ in carry]),
                np.concatenate([np.asarray(i) for _, i in carry]))

    def pair_scores(self, q_rep: jax.Array, ids: np.ndarray, q_chunk: int) -> np.ndarray:
        """Reference match scores of ``ids`` (S, m) against their own
        queries, in fixed-size chunks (the last one padded)."""
        out = []
        for c in range(0, len(ids), q_chunk):
            q_c, i_c = q_rep[c : c + q_chunk], ids[c : c + q_chunk]
            pad = q_chunk - len(i_c)
            if pad:
                q_c = jnp.concatenate([q_c, jnp.repeat(q_c[:1], pad, axis=0)])
                i_c = np.concatenate([i_c, np.repeat(i_c[:1], pad, axis=0)])
            out.append(np.asarray(self._pairs(self.arrays, self.corpus, q_c, jnp.asarray(i_c)))
                       [: q_chunk - pad])
        return np.concatenate(out)


def _merge_top(carry_v, carry_i, s, ids):
    k = carry_v.shape[1]
    v = jnp.concatenate([carry_v, s], axis=1)
    i = jnp.concatenate([carry_i, ids], axis=1)
    top_v, pos = jax.lax.top_k(v, k)
    return top_v, jnp.take_along_axis(i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("block",))
def _exact_merge(carry_v, carry_i, qn, c, start, n, block):
    x = jax.lax.dynamic_slice_in_dim(c, start, block)
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    s = jnp.dot(qn, xn.T, precision=jax.lax.Precision.HIGHEST)
    ids = start + jnp.arange(block, dtype=jnp.int32)[None, :]
    s = jnp.where(ids < n, s, -jnp.inf)
    return _merge_top(carry_v, carry_i, s, jnp.broadcast_to(ids, s.shape))


# Queries per pass of the exact search: a (chunk, block) float32 score
# matrix at a time.
EXACT_CHUNK = 1024


def exact_truth(st: Streamer, pool_q: np.ndarray, picks: np.ndarray, k: int
                ) -> Dict[int, np.ndarray]:
    """Exact cosine top-``k`` ids of each pool query in ``picks`` over the
    whole corpus, at full float32 matmul precision, streamed in blocks of
    rows; chunks of queries are padded to one shape."""
    picks = np.asarray(picks)
    if not len(picks):
        return {}
    chunk = min(EXACT_CHUNK, len(picks))
    qs = pool_q[picks]
    qs = qs / np.maximum(np.linalg.norm(qs, axis=1, keepdims=True), 1e-12)
    pad = (-len(qs)) % chunk
    qs = np.concatenate([qs, np.repeat(qs[:1], pad, axis=0)]) if pad else qs
    n = jnp.int32(st.n)
    out = []
    for c in range(0, len(qs), chunk):
        q_norm = jnp.asarray(qs[c : c + chunk], jnp.float32)
        v = jnp.full((chunk, k), -jnp.inf, jnp.float32)
        i = jnp.full((chunk, k), -1, jnp.int32)
        for start in range(0, st.corpus.shape[0], st.block):
            v, i = _exact_merge(v, i, q_norm, st.corpus, jnp.int32(start), n, block=st.block)
        out.append(np.asarray(i))
    return dict(zip(picks.tolist(), np.concatenate(out)[: len(picks)]))


def recall_at_k(answers: "Answers", truth: Dict[int, np.ndarray], k: int) -> Optional[float]:
    """Recall@k of the window's answers to the queries in ``truth``: each
    query's mean over its answers, then the mean over queries, so a hot
    query of a skewed mix counts once."""
    per_query: Dict[int, List[float]] = {}
    for p, ids in zip(answers.picks, answers.ids):
        if ids is not None and int(p) in truth:
            per_query.setdefault(int(p), []).append(len(np.intersect1d(ids[:k], truth[int(p)])) / k)
    return float(np.mean([np.mean(v) for v in per_query.values()])) if per_query else None


def _cosines(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Exact cosines in float64: rows (m, dim) against q (dim,)."""
    r = rows.astype(np.float64)
    qq = q.astype(np.float64)
    return (r @ qq) / (np.linalg.norm(r, axis=1) * np.linalg.norm(qq))


@dataclasses.dataclass
class Sample:
    """The sampled queries, normalized and encoded by the reference."""

    pool: np.ndarray      # pool indices
    raw: np.ndarray       # (S, dim) rows as sent
    q_norm: jax.Array
    q_rep: jax.Array


def prepare(ref: ModuleType, corpus: jax.Array, enc_args: dict, block: int,
            pool_q: np.ndarray, sample: np.ndarray) -> Tuple[Streamer, Sample]:
    state = ref.prepare(corpus, enc_args, block)
    qs = pool_q[sample]
    q_norm = jnp.asarray(qs / np.linalg.norm(qs, axis=1, keepdims=True), jnp.float32)
    return Streamer(ref, state, corpus, block), Sample(
        pool=np.asarray(sample), raw=qs, q_norm=q_norm, q_rep=ref.encode_queries(state, q_norm))


def compare(
    answers: Answers, st: Streamer, smp: Sample, k: int, depth: int, q_chunk: int,
    log=lambda msg: None,
) -> Dict[str, float]:
    """The four numbers over every answer whose query is in the sample."""
    t0 = time.perf_counter()
    top_v, top_i = st.topd(smp.q_rep, depth, q_chunk)
    t2 = time.perf_counter()
    row_of = {int(p): r for r, p in enumerate(smp.pool)}

    out = {"invalid": 0.0, "match_gap": 0.0, "rerank_gap": 0.0, "score_err": 0.0}
    good = []
    for j, p in enumerate(answers.picks):
        if int(p) not in row_of:
            continue
        ids = answers.ids[j]
        if ids is None:  # no answer came: the caller counts those
            continue
        if (
            len(ids) != k or np.any(ids < 0) or np.any(ids >= st.n)
            or len(np.unique(ids)) != k
        ):
            out["invalid"] += 1
        else:
            good.append((j, row_of[int(p)]))
    if not good:
        return out
    ret = np.stack([answers.ids[j] for j, _ in good]).astype(np.int32)
    rows = np.array([r for _, r in good])
    pair = st.pair_scores(smp.q_rep[jnp.asarray(rows)], ret, q_chunk)
    t3 = time.perf_counter()
    tol = st.ref.SCORE_TOL
    want = np.array(sorted(set(top_i[rows].reshape(-1).tolist()) | set(ret.reshape(-1).tolist())),
                    np.int32)
    host_rows = dict(zip(want.tolist(), np.asarray(st.corpus[jnp.asarray(want)])))
    for (j, r), m in zip(good, pair):
        best, dth = float(top_v[r, 0]), float(top_v[r, depth - 1])
        scale = max(abs(best), 1e-30)
        out["match_gap"] = max(out["match_gap"], float(np.max(dth - m)) / scale)
        ids = answers.ids[j]
        q = smp.raw[r]
        cos_ret = _cosines(np.stack([host_rows[int(i)] for i in ids]), q)
        out["score_err"] = max(
            out["score_err"], float(np.max(np.abs(answers.scores[j].astype(np.float64) - cos_ret)))
        )
        returned = set(int(x) for x in ids)
        left = [int(i) for i, v in zip(top_i[r], top_v[r])
                if v > dth + tol * scale and int(i) not in returned]
        if left:
            cos_left = _cosines(np.stack([host_rows[i] for i in left]), q)
            out["rerank_gap"] = max(out["rerank_gap"], float(cos_left.max() - cos_ret.min()))
    log(f"reference: top-{depth} {t2 - t0:.2f}s, returned ids {t3 - t2:.2f}s, "
        f"{len(good)} answers judged {time.perf_counter() - t3:.2f}s")
    return out


def _bf16(x: jax.Array) -> jax.Array:
    """Round to bfloat16 in a way XLA keeps (a cast pair may be dropped as
    excess precision on the TPU)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("k",))
def _rerank_bf16(c, qn, ids, k):
    x = c[ids]
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    s = jnp.einsum("sd,scd->sc", _bf16(qn), _bf16(xn), precision=jax.lax.Precision.HIGHEST)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(ids, pos, axis=1)


def control_answers(st: Streamer, smp: Sample, k: int, depth: int, q_chunk: int) -> Answers:
    """The precision control: the reference put in the program's place, one
    precision step below what the configuration states.  Candidates come
    from the postings as ``ref.lower_postings`` lowers them (int8 for bf16
    weights; unchanged where the postings are exact integers), and the
    rerank takes cosines of bfloat16 rows and queries, summed in float32,
    in place of float32 ones.  One answer per sampled query."""
    _, cand = st.topd(smp.q_rep, depth, q_chunk, lower=True)
    s, ids = _rerank_bf16(st.corpus, smp.q_norm, jnp.asarray(cand), k=k)
    s, ids = np.asarray(s), np.asarray(ids)
    return Answers(picks=smp.pool, ids=list(ids), scores=list(s))
