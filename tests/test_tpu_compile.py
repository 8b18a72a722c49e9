"""Mosaic compiles of the fused top-k kernels for a described TPU v5e.

Interpret mode runs a kernel body as plain JAX on the host, so it accepts
shapes, slices and primitives Mosaic refuses.  These tests compile each
kernel family at the deployed widths (T = 600 fake-word columns, T = 300
vector dims, bn = 512) for a v5e that is described, not attached, and check
that the executable holds the Mosaic kernel.  Nothing runs; results are
covered by the interpret-mode tests.

The packed search cases compile the program's own ``jit_packed_search``
(``core/packed.py``) the same way, at the served widths, and check that no
call relays out or pads the corpus: the packed view stores its match and
rerank leaves lane-aligned and row-major.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bruteforce
from repro.core import packed as packed_mod
from repro.core.segments import IndexWriter
from repro.core.types import BruteForceConfig, FakeWordsConfig, LexicalLshConfig
from repro.kernels import common
from repro.kernels.fused_topk import kernel as K

N = 65_536   # doc rows: 128 doc tiles of bn = 512
B = 256      # query batch of the ann cells
R = 1_024    # gathered candidate rows per query (blockmax stage 2)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


CASES = {
    # classic fake words: bf16 postings, the served (N,) live bitmap
    "classic_bf16_t600_d100": (
        lambda q, d, f: K.fused_topk(q, d, 100, filt=f, interpret=False),
        [((B, 600), jnp.bfloat16), ((N, 600), jnp.bfloat16), ((N,), jnp.bool_)],
    ),
    # dot-mode fake words: int8 MXU path
    "dot_int8_t600_d100": (
        lambda q, d: K.fused_topk(q, d, 100, interpret=False),
        [((B, 600), jnp.int8), ((N, 600), jnp.int8)],
    ),
    # brute-force cosine: f32 vectors
    "cosine_f32_t300_d100": (
        lambda q, d: K.fused_topk(q, d, 100, interpret=False),
        [((B, 300), jnp.float32), ((N, 300), jnp.float32)],
    ),
    # lexical LSH: uint32 signature collision counts
    "lsh_t300_d100": (
        lambda q, d: K.fused_topk(q, d, 100, mode="lsh", interpret=False),
        [((B, 300), jnp.uint32), ((N, 300), jnp.uint32)],
    ),
    # int4 postings, group 32: 600 columns packed into 300 bytes
    "int4_t600_d100": (
        lambda q, d, s: K.fused_topk_quantized(
            q, d, s, 100, bits=4, group=32, interpret=False
        ),
        [((B, 600), jnp.bfloat16), ((N, 300), jnp.uint8), ((N, 20), jnp.float32)],
    ),
    # blockmax stage 2 / graph search: per-query gathered rows
    "gathered_bf16_t600_d400": (
        lambda q, d, r: K.fused_topk_gathered(q, d, r, 400, N, interpret=False),
        [((B, 600), jnp.bfloat16), ((B, R, 600), jnp.bfloat16), ((B, R), jnp.int32)],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


# Served configurations at 200-d: classic fake words (400 bf16 postings +
# 200 f32 rerank rows), LSH (300 uint32 slots + 200 f32), brute force (200
# f32 matched and reranked).
PACKED = {
    "classic_t400": FakeWordsConfig(quantization=50),
    "lsh_s300": LexicalLshConfig(buckets=300, hashes=1),
    "bruteforce_d200": BruteForceConfig(),
}
DIM = 200

# An N-row array produced by a relayout: pad, copy or transpose.  (The
# async copy-start/copy-done pairs move the 1-byte-a-row live bitmap
# between memory spaces; they relay nothing out.)
_RELAYOUT = re.compile(
    r"^\s*(?:ROOT )?%\S+ = \w+\[(\d+)[,\]].*?\s(pad|copy|transpose)\("
)


class _DescribedChip(packed_mod.ExecutableCache):
    """Compiles what the packed search asks for with N corpus rows and B
    queries, for the described chip, and runs nothing."""

    def __init__(self, sharding):
        super().__init__()
        self.sharding = sharding
        self.exe = None

    def get(self, key, build_fn, args, donate_argnums=()):
        view, live, fm, q_rep, q_norm, bm = args
        bucket = live.shape[0]

        def spec(x, rows=None):
            shape = x.shape if rows is None else (rows,) + x.shape[1:]
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=self.sharding)

        view = jax.tree.map(
            lambda x: spec(x, N if x.ndim and x.shape[0] == bucket else None),
            view,
        )
        args = (view, spec(live, N), fm, spec(q_rep, B), spec(q_norm, B), bm)
        self.exe = super().get(key, build_fn, args, donate_argnums)
        return lambda *_: (None, None)


@pytest.mark.parametrize("case", sorted(PACKED))
def test_packed_search_keeps_corpus_in_place(one_chip, case, monkeypatch):
    """The served packed search (deletes-or-padding live bitmap, depth 100,
    exact rerank) compiles for the v5e with no pad, copy or transpose of
    the corpus and with compiled scratch under 1% of its arguments."""
    rng = np.random.default_rng(0)
    w = IndexWriter(PACKED[case], rerank_store="exact", merge_policy=None)
    w.add(rng.normal(size=(200, DIM)).astype(np.float32))  # bucket 256
    reader = w.refresh()
    pk = reader.packed_segments()
    q = bruteforce.l2_normalize(
        jnp.asarray(rng.normal(size=(8, DIM)).astype(np.float32)))
    chip = _DescribedChip(one_chip)
    monkeypatch.setattr(common, "INTERPRET", False)
    packed_mod.packed_search(
        pk, reader.pipeline, reader._packed_matcher(), q, k=10, depth=100,
        rerank=True, quantized=False, use_kernel=True, cache=chip,
    )
    text = chip.exe.as_text()
    assert text.startswith("HloModule jit_packed_search,")
    assert "tpu_custom_call" in text
    relayouts = [
        line.strip()[:160] for line in text.splitlines()
        if (m := _RELAYOUT.match(line)) and int(m.group(1)) == N
    ]
    assert not relayouts, relayouts
    mem = chip.exe.memory_analysis()
    assert chip.temp_bytes(chip.exe) == mem.temp_size_in_bytes
    assert mem.temp_size_in_bytes < 0.01 * mem.argument_size_in_bytes, mem


# The doc-sharded word2vec cell: 2,999,808 rows over four chips, 786,432
# bucket rows a chip, 600 fake-word columns stored at 1,024, 300-d rerank
# rows stored at 384.
SHARD_ROWS = 786_432
SHARDS = 4
DEPTH = 100
W2V_DIM = 300


@pytest.fixture(scope="module")
def four_chips(one_chip):
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices[:SHARDS]), ("shard",),
                axis_types=(jax.sharding.AxisType.Auto,))


# A collective in the compiled text: its result shape and opcode.
_COLLECTIVE = re.compile(
    r"= (\w+)\[([\d,]*)\][^=]*?\s(all-gather|all-reduce|collective-permute|"
    r"all-to-all|reduce-scatter)(?:-start)?\("
)


def test_sharded_packed_search_merges_only_the_candidates(four_chips, monkeypatch):
    """The doc-sharded search of the word2vec cell compiles for a v5e 2x2:
    each chip's kernel streams its own rows, every collective moves a
    (B, depth)-sized operand (no N-row all-gather, copy or pad), and the
    compiled scratch stays under 1% of the arguments."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed, pipeline as pl
    from repro.core.types import FakeWordsIndex

    mesh = four_chips
    rows = SHARD_ROWS * SHARDS

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    doc, rep = P("shard", None), P()
    view = FakeWordsIndex(
        tf=sds((rows, 600), jnp.int8, doc), idf=sds((600,), jnp.float32, rep),
        norm=sds((rows,), jnp.float32, P("shard")), df=sds((600,), jnp.int32, rep),
        scored=sds((rows, K.aligned_width(600)), jnp.bfloat16, doc),
        vectors=sds((rows, K.aligned_width(W2V_DIM)), jnp.float32, doc),
    )
    monkeypatch.setattr(common, "INTERPRET", False)
    fn = packed_mod.sharded_search_fn(
        mesh, distributed.index_pspec(view, ("shard",)),
        pl.make_matcher(FakeWordsConfig(quantization=50)), DEPTH, 10,
        rerank=True, quantized=False, use_kernel=True,
    )
    exe = jax.jit(fn).lower(
        view, sds((rows,), jnp.bool_, P("shard")), sds((rows,), jnp.int32, P("shard")),
        None, sds((B, 600), jnp.int32, rep), sds((B, W2V_DIM), jnp.float32, rep),
    ).compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text
    collectives = _COLLECTIVE.findall(text)
    assert {op for _, _, op in collectives} >= {"all-gather", "all-reduce"}
    for dtype, dims, op in collectives:
        n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        assert n <= B * DEPTH * SHARDS, (dtype, dims, op)
    relayouts = [
        line.strip()[:160] for line in text.splitlines()
        if (m := _RELAYOUT.match(line)) and int(m.group(1)) in (SHARD_ROWS, rows)
    ]
    assert not relayouts, relayouts
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes < 0.01 * mem.argument_size_in_bytes, mem
