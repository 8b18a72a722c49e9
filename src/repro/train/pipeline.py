"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Layers are split into S contiguous stages (one per pipe shard); a global
batch is cut into M microbatches that flow through stages with
``collective_permute`` handoffs.  Schedule: plain GPipe (fill, steady state,
drain — S+M-1 ticks); bubble fraction = (S-1)/(S+M-1).

Implementation notes
--------------------
* Everything runs inside one ``shard_map`` over the 'pipe' axis: each shard
  holds its stage's layer stack (leading n_layers/S axis) and scans over it.
* The tick loop is a ``lax.scan`` over S+M-1 ticks, carrying a rolling
  (M, ...) microbatch buffer; shard i computes real work only for ticks in
  [i, i+M) — selected by masks (no data-dependent control flow).
* The backward pass comes from jax.grad through the whole scan — the
  forward activations are rematerialized per-stage (jax.checkpoint around
  the stage body), which is exactly GPipe's activation recomputation.

This module is exercised by tests/test_pipeline.py at small scale and by the
pp variant configs in the dry-run; the default production mesh keeps
pipe=1 (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


def stage_params(params_stacked: Pytree, stage: jax.Array, n_stages: int) -> Pytree:
    """Slice a (n_layers, ...) stacked layer tree to this stage's
    (n_layers/S, ...) block.  Runs inside shard_map."""

    def slc(x):
        per = x.shape[0] // n_stages
        return jax.lax.dynamic_slice_in_dim(x, stage * per, per, axis=0)

    return jax.tree_util.tree_map(slc, params_stacked)


def gpipe_apply(
    layer_fn: Callable[[jax.Array, Pytree], jax.Array],
    params_stacked: Pytree,  # (n_layers, ...) leaves, replicated or sharded
    x: jax.Array,            # (M, mb, ...) microbatched activations
    n_stages: int,
    axis: str = "pipe",
) -> jax.Array:
    """Run the pipeline inside shard_map over ``axis``; returns final
    activations (M, mb, ...) valid on the LAST stage (replicated out by the
    caller's out_spec or used directly for the loss there)."""
    stage = jax.lax.axis_index(axis)
    m = x.shape[0]
    my_layers = stage_params(params_stacked, stage, n_stages)

    def stage_body(h):
        def scan_layer(h, layer):
            return layer_fn(h, layer), None

        h, _ = jax.lax.scan(scan_layer, h, my_layers)
        return h

    stage_body = jax.checkpoint(stage_body)

    n_ticks = n_stages + m - 1
    first, last = stage == 0, stage == n_stages - 1

    def tick(carry, t):
        buf, out = carry  # buf: (M, mb, ...) input queue view; out: results
        mb_idx = t - stage  # which microbatch this stage works on at tick t
        active = (mb_idx >= 0) & (mb_idx < m)
        h_in = jax.lax.dynamic_index_in_dim(buf, jnp.clip(mb_idx, 0, m - 1), 0, keepdims=False)
        h_out = stage_body(h_in)
        h_out = jnp.where(active, h_out, h_in)
        # pass result to the next stage's buffer slot (ring permute).
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        h_next = jax.lax.ppermute(h_out, axis, perm)
        # Non-first stages overwrite their queue slot for microbatch t+1-stage.
        recv_idx = jnp.clip(mb_idx + 1, 0, m - 1)
        buf = jnp.where(
            first,
            buf,
            jax.lax.dynamic_update_index_in_dim(buf, h_next, recv_idx, 0),
        )
        out = jnp.where(
            last & active,
            jax.lax.dynamic_update_index_in_dim(out, h_out, jnp.clip(mb_idx, 0, m - 1), 0),
            out,
        )
        return (buf, out), None

    out0 = jnp.zeros_like(x)
    (buf, out), _ = jax.lax.scan(tick, (x, out0), jnp.arange(n_ticks))
    # Results live on the last stage only; broadcast so the out_spec's
    # "replicated" claim is true (one (M, mb, ...) all-reduce).
    return jax.lax.psum(jnp.where(last, out, jnp.zeros_like(out)), axis)


def build_gpipe_fn(
    mesh: Mesh,
    layer_fn: Callable[[jax.Array, Pytree], jax.Array],
    n_stages: int,
    axis: str = "pipe",
    batch_axes: Tuple[str, ...] = (),
):
    """shard_map wrapper: params replicated over 'pipe' (each stage slices
    its block), activations microbatched on the host side."""

    def fn(params_stacked, x):
        return gpipe_apply(layer_fn, params_stacked, x, n_stages, axis)

    in_specs = (P(), P(None, batch_axes if batch_axes else None))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=P(None, batch_axes if batch_axes else None),
        check_vma=False,
    )


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)
