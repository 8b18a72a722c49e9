"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state: the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches see the real single device.
"""
from __future__ import annotations

import jax

from repro.core.distributed import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading pod=2 axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Shrunk mesh with the same axis names for CPU multi-device tests
    (requires >= 8 host devices via XLA_FLAGS)."""
    n = len(jax.devices())
    if multi_pod:
        assert n >= 8
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert n >= 4
    return make_mesh((2, 2), ("data", "model"))
