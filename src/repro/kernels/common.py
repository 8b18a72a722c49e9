"""Shared Pallas kernel utilities.

Kernels here target TPU (MXU 128x128 systolic matmul, VMEM tiling via
BlockSpec) but are validated on CPU with ``interpret=True``, which executes
the kernel body in Python.  ``INTERPRET`` flips globally for tests.
"""
from __future__ import annotations

import os
from typing import Any

import jax

# Pallas kernels compile through Mosaic on a TPU and run in interpret mode
# everywhere else (the kernel body executes on the host, for tests on CPU).
INTERPRET = jax.default_backend() != "tpu"

# Default for the ``use_kernel`` routing flags on the search hot paths: the
# fused Pallas path on real TPUs, the XLA reference path elsewhere (tests
# opt in explicitly and run the kernels in interpret mode).
# ``REPRO_USE_KERNEL=1`` forces the kernel path off-TPU too (paired with
# interpret mode this lets CI exercise the Pallas kernel bodies on CPU).
USE_KERNEL_DEFAULT = jax.default_backend() == "tpu" or bool(
    int(os.environ.get("REPRO_USE_KERNEL", "0"))
)

# MXU/VPU-aligned default tiles.
LANE = 128
SUBLANE_F32 = 8
SUBLANE_BF16 = 16
SUBLANE_INT8 = 32


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (bitonic networks need pow2 lengths)."""
    return 1 << max(0, (x - 1).bit_length())


# --------------------------------------------------------------------------
# Canonical int4 nibble unpack / grouped-scale dequantization
# (docs/DESIGN.md §12).  Lives here — the dependency-free kernel utility
# module — so the XLA reference scoring paths and the build-time quantizer
# (core/builder.py) run the EXACT same operation sequence: bit-for-bit
# identical dequantized operands.  The Pallas kernel tiles repeat the same
# arithmetic per value in a [low | high] nibble layout
# (``fused_topk.kernel._dequant_tile``).
# --------------------------------------------------------------------------


def unpack_int4(packed: jax.Array) -> jax.Array:
    """uint8 nibble pairs -> interleaved nibble columns (..., 2C) uint8.

    Low nibble = even column, high nibble = odd column; interleaving is a
    stack + reshape (gather-free).  XLA paths only: Mosaic has no lane
    interleave, so the kernels dequantize low and high nibbles as separate
    halves (``fused_topk.kernel._dequant_tile``)."""
    import jax.numpy as jnp

    lo = packed & jnp.uint8(0xF)
    hi = packed >> 4
    shape = packed.shape[:-1] + (2 * packed.shape[-1],)
    return jnp.stack([lo, hi], axis=-1).reshape(shape)


def expand_group_scale(scale: jax.Array, group: int) -> jax.Array:
    """(..., G) per-group scales -> (..., G*group) per-column, via broadcast
    + reshape (no gathers)."""
    import jax.numpy as jnp

    shape = scale.shape[:-1] + (scale.shape[-1], group)
    return jnp.broadcast_to(scale[..., None], shape).reshape(
        scale.shape[:-1] + (scale.shape[-1] * group,)
    )


def dequant_int4(
    packed: jax.Array, scale: jax.Array, group: int, dtype: Any
) -> jax.Array:
    """THE canonical int4 grouped-scale dequant ordering: f32 (nibble - 8)
    * group_scale, then ONE cast to the compute dtype.  (..., C) packed +
    (..., 2C/group) scales -> (..., 2C) values."""
    import jax.numpy as jnp

    nib = unpack_int4(packed).astype(jnp.float32) - 8.0
    return (nib * expand_group_scale(scale, group)).astype(dtype)


def pad_dim(x: jax.Array, axis: int, multiple: int, value: Any = 0) -> jax.Array:
    """Zero-pad ``axis`` of x up to a multiple (kernels want aligned tiles)."""
    import jax.numpy as jnp

    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
