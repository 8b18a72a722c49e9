"""Mosaic compiles of the fused top-k kernels for a described TPU v5e.

Interpret mode runs a kernel body as plain JAX on the host, so it accepts
shapes, slices and primitives Mosaic refuses.  These tests compile each
kernel family at the deployed widths (T = 600 fake-word columns, T = 300
vector dims, bn = 512) for a v5e that is described, not attached, and check
that the executable holds the Mosaic kernel.  Nothing runs; results are
covered by the interpret-mode tests.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
