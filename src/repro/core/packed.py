"""Packed single-launch segmented search (docs/DESIGN.md §14).

The per-segment loop in :mod:`repro.core.segments` is faithful Lucene — and
pays Lucene's launch tax on an accelerator: a 16-segment NRT index costs 16
matcher dispatches, 16 device round-trips, and a host-side merge per query
batch.  This module packs every live segment's stat view into ONE padded
superbuffer so the fused streaming top-k launches once per batch regardless
of segment count:

  * **Layout.**  Per-doc leaves (postings, signatures, reduced points,
    rerank stores) concatenate in GLOBAL-ID ORDER with no inter-segment
    padding, so packed row ``g`` IS global doc id ``g`` — the offset remap
    is the identity by construction and the kernel emits global ids
    directly.  Global leaves (df/idf, the fitted reduction) come from the
    stat views, which already share them across segments.
  * **Lane-aligned leaves.**  Every per-doc leaf the search streams whole
    (the match operand) or gathers rows from (the f32 rerank rows) is
    stored at the fused kernels' reduce width
    (``fused_topk.kernel.aligned_width``), padded with the filler the kernel
    wrappers would pad with per call.  XLA then keeps it row-major, so a
    call neither relays out nor pads the corpus; every consumer that reads
    it without a kernel pass keeps the logical width by padding the query
    (exact) or slicing the gathered candidates, never the corpus.
  * **Bucket ladder.**  Only the tail pads, up to a small geometric ladder
    (powers of two and 1.5x steps, ≤ 33% overhead), so executable shapes
    recur across flush/merge/refresh cycles instead of recompiling per
    corpus size.  Tail rows are zeros and can never rank: they are masked
    through the same in-kernel ``filt`` bitmap that masks deletes (dynamic
    content, static shape — no recompile per add), or via the kernels'
    static ``n_docs`` ragged-row bound for shape-static callers.
  * **Executable cache.**  A bounded, explicitly keyed LRU of AOT-compiled
    executables (:class:`ExecutableCache`); the key is (static knobs,
    pytree structure, leaf avals), so refresh cycles within one bucket are
    zero-compile.  ``EXEC_CACHE.compiles`` makes the recompile-guard test
    honest.  Each compile records the executable's temp bytes (on the
    ``packed.compile`` span and, for the search, on the snapshot): a
    whole-corpus relayout inside the search shows up there.
  * **Donated incremental repack.**  For stats-static encodings (dot-mode
    fake words, LSH, brute force) a refresh that only appends segments
    reuses the previous snapshot's packed buffers via a donated
    ``dynamic_update_slice`` — the superbuffer is updated in place instead
    of re-concatenated (classic/kd views rebuild per-row state under new
    global stats, so they repack fully).

Parity: per-row scores are row-local reductions, so packing rows does not
change them; global-id ordering + ``lax.top_k``'s stable ties reproduce the
loop's segment-major merge tie-break; the rerank gathers the identical rows
into the identical candidate positions and runs the identical einsum.  The
per-segment loop remains available (``search(packed=False)``) as the
reference path and serves any layout this module rejects.
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import distributed
from repro.core.types import (
    BruteForceConfig,
    FakeWordsConfig,
    KdTreeConfig,
    LexicalLshConfig,
    QuantizedPostings,
    QuantizedStore,
)

__all__ = [
    "PackedUnsupported",
    "PackedSegments",
    "ExecutableCache",
    "EXEC_CACHE",
    "bucket_rows",
    "pack_segments",
    "pack_segments_sharded",
    "packed_search",
    "packed_blockmax",
]


class PackedUnsupported(ValueError):
    """This snapshot cannot ride the packed single-launch path (mixed
    per-segment store layouts, per-segment statistics, ...); callers fall
    back to the per-segment loop."""


# --------------------------------------------------------------------------
# Bucket ladder
# --------------------------------------------------------------------------

BUCKET_FLOOR = 256


def bucket_rows(n: int, floor: int = BUCKET_FLOOR) -> int:
    """Round a row count up the geometric ladder {floor, ..., 2^k, 3·2^k-1}
    (powers of two interleaved with their 1.5x midpoints).  Worst-case pad
    overhead is 33%; in exchange, every snapshot whose total lands in the
    same rung reuses the same compiled executables."""
    if n <= floor:
        return floor
    p = 1 << (n - 1).bit_length()  # next power of two >= n
    mid = 3 * (p // 4)             # 1.5 * previous power of two
    return mid if mid >= n else p


def _append_block(n: int, room: int = 1 << 30, floor: int = 128) -> int:
    """Pad an appended segment block to a power of two so the donated
    incremental-repack executable recompiles per block RUNG, not per flush
    size.  Near the top of the bucket the preferred rung may overhang the
    remaining ``room`` even though the rows themselves fit; halve down to
    the largest rung that fits (>= 8 rows, the f32 sublane) instead of
    forcing callers into a full repack — each smaller rung costs at most
    one extra compile per encoding, ever.  Returns 0 when no aligned rung
    can hold ``n`` rows in ``room``."""
    block = max(floor, 1 << (n - 1).bit_length())
    while block > room and block >= 16:
        block //= 2
    if block > room or block < n:
        return 0
    return block


# --------------------------------------------------------------------------
# Leaf packing
# --------------------------------------------------------------------------


def _cat_pad(
    parts: Sequence[jax.Array], rows: int, align: bool = False
) -> jax.Array:
    """Concatenate per-segment per-doc leaves along rows and zero-pad the
    tail to ``rows``.  Pad rows are masked at search time, and the donated
    append path overwrites them.  ``align`` also widens the minor axis to
    the fused kernels' reduce width, in the same pass."""
    from repro.kernels.fused_topk import ops as fused

    x = parts[0] if len(parts) == 1 else jnp.concatenate(list(parts), axis=0)
    pad = rows - x.shape[0]
    if pad < 0:
        raise PackedUnsupported(
            f"segment rows {x.shape[0]} exceed bucket {rows}"
        )
    if align:
        return fused.lane_align(x, rows)
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
    )


def _all_or_none(views: Sequence[Any], name: str) -> Optional[List[Any]]:
    vals = [getattr(v, name) for v in views]
    if all(v is None for v in vals):
        return None
    if any(v is None for v in vals):
        raise PackedUnsupported(
            f"mixed per-segment presence of {name!r} (some segments carry "
            "it, some do not) — per-segment loop only"
        )
    return vals


def _pack_vq(views: Sequence[Any], rows: int) -> Optional[QuantizedStore]:
    vqs = _all_or_none(views, "vq")
    if vqs is None:
        return None
    return QuantizedStore(
        q=_cat_pad([s.q for s in vqs], rows),
        scale=_cat_pad([s.scale for s in vqs], rows),
    )


def _pack_pq(views: Sequence[Any], rows: int) -> Optional[QuantizedPostings]:
    pqs = _all_or_none(views, "pq")
    if pqs is None:
        return None
    meta = {(p.bits, p.group, p.cols, p.q.shape[1:]) for p in pqs}
    if len(meta) > 1:
        raise PackedUnsupported(
            f"segments disagree on quantized-postings layout: {sorted(meta)}"
        )
    return dataclasses.replace(
        pqs[0],
        q=_cat_pad([p.q for p in pqs], rows),
        scale=_cat_pad([p.scale for p in pqs], rows),
    )


def _packed_view(config, views: Sequence[Any], rows: int):
    """One synthetic index view with every per-doc leaf packed to ``rows``;
    global leaves (df/idf/reduction) carry over from the stat views.  The
    match operand and the f32 rerank rows are stored lane-aligned."""
    if not isinstance(
        config, (FakeWordsConfig, LexicalLshConfig, KdTreeConfig,
                 BruteForceConfig)
    ):
        raise PackedUnsupported(
            f"no packed layout for config type {type(config).__name__}"
        )
    v0 = views[0]
    repl: Dict[str, Any] = {"vq": _pack_vq(views, rows)}
    vecs = _all_or_none(views, "vectors")
    repl["vectors"] = None if vecs is None else _cat_pad(vecs, rows, True)
    if isinstance(config, FakeWordsConfig):
        repl["pq"] = _pack_pq(views, rows)
        repl["norm"] = _cat_pad([v.norm for v in views], rows)
        match = "scored" if config.scoring == "classic" else "tf"
        for name in ("tf", "scored"):
            vals = _all_or_none(views, name)
            repl[name] = None if vals is None else _cat_pad(
                vals, rows, name == match
            )
        return dataclasses.replace(v0, **repl)
    if isinstance(config, LexicalLshConfig):
        repl["sig"] = _cat_pad([v.sig for v in views], rows, True)
        return dataclasses.replace(v0, **repl)
    if isinstance(config, KdTreeConfig):
        from repro.kernels.fused_topk import ops as fused

        repl["reduced"] = _cat_pad([v.reduced for v in views], rows)
        repl["lifted"] = _cat_pad(
            [
                v.lifted if v.lifted is not None else fused.lift_l2(v.reduced)
                for v in views
            ],
            rows,
            True,
        )
        repl["split_dim"] = repl["split_val"] = repl["perm"] = None
        return dataclasses.replace(v0, **repl)
    repl["pq"] = _pack_pq(views, rows)
    if repl["vectors"] is None and repl["pq"] is None:
        raise PackedUnsupported(
            "brute-force segments carry neither vectors nor postings"
        )
    return dataclasses.replace(v0, **repl)


def _doc_leaf_paths(config, view) -> List[Tuple[str, ...]]:
    """Attribute paths of every per-doc leaf present on a packed view (the
    leaves the donated incremental repack must update in place)."""
    names = {
        FakeWordsConfig: ("tf", "scored", "norm", "vectors"),
        LexicalLshConfig: ("sig", "vectors"),
        KdTreeConfig: ("reduced", "lifted", "vectors"),
        BruteForceConfig: ("vectors",),
    }[type(config)]
    paths: List[Tuple[str, ...]] = [
        (n,) for n in names if getattr(view, n, None) is not None
    ]
    for store in ("vq", "pq"):
        s = getattr(view, store, None)
        if s is not None:
            paths += [(store, "q"), (store, "scale")]
    return paths


def _get_path(view, path: Tuple[str, ...]):
    x = view
    for p in path:
        x = getattr(x, p)
    return x


def _replace_paths(view, updates: Dict[Tuple[str, ...], jax.Array]):
    """Rebuild a view with the given (possibly nested) leaves replaced."""
    top: Dict[str, Any] = {}
    nested: Dict[str, Dict[str, Any]] = {}
    for path, val in updates.items():
        if len(path) == 1:
            top[path[0]] = val
        else:
            nested.setdefault(path[0], {})[path[1]] = val
    for store, fields in nested.items():
        top[store] = dataclasses.replace(getattr(view, store), **fields)
    return dataclasses.replace(view, **top)


# --------------------------------------------------------------------------
# Executable cache
# --------------------------------------------------------------------------


class ExecutableCache:
    """Bounded LRU of AOT-compiled executables, explicitly keyed.

    jit's implicit cache already avoids recompiles — per live function
    object.  The packed path rebuilds its staged closures per snapshot, so
    it needs a cache keyed on what ACTUALLY determines the executable:
    static knobs + pytree structure + leaf avals.  AOT ``lower().compile()``
    on miss makes ``compiles`` an honest counter (a cache hit can never
    silently recompile), which is what the recompile-guard test asserts
    against."""

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self._temp: Dict[int, Optional[int]] = {}  # id(exe) -> temp bytes
        self.hits = 0
        self.compiles = 0
        self.evictions = 0

    @staticmethod
    def _avals(args) -> Tuple[Any, Tuple]:
        flat, treedef = jax.tree_util.tree_flatten(args)
        return treedef, tuple(
            (tuple(x.shape), jnp.result_type(x).name) for x in flat
        )

    def get(self, key, build_fn, args, donate_argnums: Tuple[int, ...] = ()):
        """The compiled executable for ``key`` + the avals of ``args``;
        builds (and AOT-compiles) via ``build_fn()`` on miss."""
        full_key = (key, donate_argnums, self._avals(args))
        hit = self._entries.get(full_key)
        if hit is not None:
            self._entries.move_to_end(full_key)
            self.hits += 1
            return hit
        with obs.span("packed.compile", kind=key[0]) as sp:
            exe = jax.jit(build_fn(), donate_argnums=donate_argnums).lower(
                *args
            ).compile()
            mem = exe.memory_analysis()
            temp = None if mem is None else mem.temp_size_in_bytes
            sp.set_metadata(temp_bytes=temp)
        self.compiles += 1
        self._entries[full_key] = exe
        self._temp[id(exe)] = temp
        while len(self._entries) > self.capacity:
            _, old = self._entries.popitem(last=False)
            self._temp.pop(id(old), None)
            self.evictions += 1
        return exe

    def temp_bytes(self, exe) -> Optional[int]:
        """Compiled scratch (``temp_size_in_bytes``) of a cached executable;
        None once evicted or where the backend reports none."""
        return self._temp.get(id(exe))

    def executables(self, kind: str) -> List[Any]:
        """The cached executables of one kind ("search" or "append")."""
        return [exe for (key, _, _), exe in self._entries.items()
                if key[0] == kind]

    def clear(self) -> None:
        self._entries.clear()
        self._temp.clear()
        self.hits = self.compiles = self.evictions = 0

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "compiles": self.compiles,
            "evictions": self.evictions,
        }


#: Process-wide cache shared by every packed reader (snapshots of one
#: writer land in the same rungs, so sharing is the point).
EXEC_CACHE = ExecutableCache(
    capacity=int(os.environ.get("REPRO_PACKED_CACHE", "64"))
)


# --------------------------------------------------------------------------
# Packed snapshot state
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PackedSegments:
    """One snapshot's packed superbuffer + the masks that make it honest.

    ``view`` is a synthetic single-segment index view with ``bucket`` rows:
    rows [0, n_rows) are the segments' rows in global-id order, rows
    [n_rows, bucket) are zero padding.  ``live`` composes liveDocs ∧
    row-validity into the one bitmap the kernels take."""

    view: Any
    bucket: int
    n_rows: int                    # reader.max_doc (deleted rows included)
    n_live: int                    # reader.num_docs (live rows only)
    live: jax.Array                # (bucket,) bool: live ∧ row < n_rows
    any_deleted: bool
    seg_names: Tuple[str, ...]
    seg_rows: Tuple[int, ...]
    appends: int = 0               # donated incremental repacks absorbed
    search_temp_bytes: Optional[int] = None  # of the last search executable
    bm_cache: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # Doc-sharded snapshots (pack_segments_sharded): ``bucket`` is each
    # chip's, ``view`` and ``live`` hold ``shards * bucket`` rows sharded
    # over ``mesh``, ``ids`` maps each chip's local row to its global doc
    # id (-1 = pad), and ``shard_live`` counts each chip's live rows.
    mesh: Any = None
    ids: Optional[jax.Array] = None
    shard_live: Tuple[int, ...] = ()

    @property
    def shards(self) -> int:
        """Chips the rows are spread over (1 on one device)."""
        return 1 if self.mesh is None else self.mesh.size

    @property
    def full(self) -> bool:
        """No pad rows and no deletes: the packed view needs no masking at
        all and dispatches the exact unfiltered monolithic call graph."""
        return (not self.any_deleted) and self.n_rows == self.bucket


def stats_static(config) -> bool:
    """Encodings whose stat views keep per-doc leaves untouched across
    refreshes (only GLOBAL leaves move), making append-only incremental
    repack sound.  Classic fake words rebuild ``scored``/``pq`` per row
    under new global idf; the kd reduction refits — both repack fully."""
    if isinstance(config, (LexicalLshConfig, BruteForceConfig)):
        return True
    return isinstance(config, FakeWordsConfig) and config.scoring != "classic"


def _global_leaf_updates(config, views) -> Dict[Tuple[str, ...], jax.Array]:
    """Global (non-per-doc) leaves an incremental repack must refresh from
    the new stat views: dot-mode fake words re-derive df/idf over the new
    live set."""
    if isinstance(config, FakeWordsConfig):
        return {("df",): views[0].df, ("idf",): views[0].idf}
    return {}


def _live_bitmap(segments, n_rows: int, bucket: int) -> jax.Array:
    live = np.zeros(bucket, bool)
    base = 0
    for s in segments:
        live[base : base + s.num_docs] = s.live
        base += s.num_docs
    assert base == n_rows
    return jnp.asarray(live)


def _try_append(
    config, views, segments, prior: "PackedSegments",
    names: Tuple[str, ...], rows: Tuple[int, ...], bucket: int, n_rows: int,
) -> Optional["PackedSegments"]:
    """Absorb an append-only refresh into the prior snapshot's buffers via
    a donated dynamic_update_slice; None when ineligible (full repack)."""
    k = len(prior.seg_names)
    if not (
        stats_static(config)
        and bucket == prior.bucket
        and len(names) > k
        and names[:k] == prior.seg_names
        and rows[:k] == prior.seg_rows
    ):
        return None
    offset = prior.n_rows
    new_rows = n_rows - offset
    block = _append_block(new_rows, room=bucket - offset)
    if not block:
        return None  # no aligned rung fits: dynamic_update_slice clamps
        # starts, so an overhanging block must never be risked
    paths = _doc_leaf_paths(config, prior.view)
    new_view = _packed_view(config, views[k:], block)
    old_leaves = tuple(_get_path(prior.view, p) for p in paths)
    new_leaves = tuple(_get_path(new_view, p) for p in paths)
    if any(o.shape[1:] != n.shape[1:] or o.dtype != n.dtype
           for o, n in zip(old_leaves, new_leaves)):
        return None

    def build():
        # Named for the trace: the executable's module is jit_packed_append.
        def packed_append(old, new, off):
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(o, nw, off, axis=0)
                for o, nw in zip(old, new)
            )
        return packed_append

    off_dev = jnp.int32(offset)
    exe = EXEC_CACHE.get(
        ("append", type(config).__name__, tuple(paths)),
        build, (old_leaves, new_leaves, off_dev), donate_argnums=(0,),
    )
    updated = exe(old_leaves, new_leaves, off_dev)
    view = _replace_paths(prior.view, dict(zip(paths, updated)))
    view = _replace_paths(view, _global_leaf_updates(config, views))
    # The prior snapshot's buffers are donated: neuter it so a stale reader
    # lazily repacks instead of touching freed memory.
    prior.view = None
    any_del = any(s.del_count for s in segments)
    return PackedSegments(
        view=view, bucket=bucket, n_rows=n_rows,
        n_live=sum(s.num_live for s in segments),
        live=_live_bitmap(segments, n_rows, bucket),
        any_deleted=any_del, seg_names=names, seg_rows=rows,
        appends=prior.appends + 1,
    )


def pack_segments(
    config,
    views: Sequence[Any],
    segments: Sequence[Any],
    global_stats: bool = True,
    prior: Optional["PackedSegments"] = None,
) -> PackedSegments:
    """Pack a snapshot's stat views into one superbuffer.  Raises
    :class:`PackedUnsupported` for layouts the single-launch path cannot
    serve exactly (per-segment statistics, mixed store presence)."""
    if not segments:
        raise PackedUnsupported("no segments to pack")
    if not global_stats and not isinstance(
        config, (LexicalLshConfig, BruteForceConfig)
    ):
        raise PackedUnsupported(
            "global_stats=False scores each segment under its own "
            "statistics — one packed launch cannot reproduce per-segment "
            "query operands"
        )
    names = tuple(s.name for s in segments)
    rows = tuple(s.num_docs for s in segments)
    n_rows = sum(rows)
    bucket = bucket_rows(n_rows)
    if prior is not None and prior.view is not None:
        with obs.span("packed.append", rows=n_rows - prior.n_rows,
                      segments=len(segments)):
            inc = _try_append(
                config, views, segments, prior, names, rows, bucket, n_rows
            )
        if inc is not None:
            return inc
    with obs.span("packed.pack", rows=n_rows, segments=len(segments),
                  shards=1, chip_rows=n_rows):
        view = _packed_view(config, views, bucket)
        return PackedSegments(
            view=view, bucket=bucket, n_rows=n_rows,
            n_live=sum(s.num_live for s in segments),
            live=_live_bitmap(segments, n_rows, bucket),
            any_deleted=any(s.del_count for s in segments),
            seg_names=names, seg_rows=rows,
        )


def _shard_ids(segments, shards: int, bucket: int) -> np.ndarray:
    """(shards, bucket) global doc id of each chip's local row, -1 for pad
    rows: each segment contributes its block of rows on every chip, in
    order (``Segment.stored_rows`` says where each doc is stored)."""
    ids = np.full((shards, bucket), -1, np.int32)
    base = off = 0
    for seg in segments:
        block = seg.ann.num_docs // shards
        stored = seg.stored_rows()
        ids[stored // block, off + stored % block] = base + np.arange(seg.num_docs)
        off += block
        base += seg.num_docs
    return ids


def pack_segments_sharded(
    config, views, segments, mesh, cache: Optional[ExecutableCache] = None
) -> PackedSegments:
    """Pack a doc-sharded snapshot per chip: each chip concatenates its own
    rows of every segment and pads them to its own bucket, through the same
    :func:`_packed_view` as one device, under ``shard_map``; the live bitmap
    and the local-row -> global-id map are sharded with the rows.  No chip
    sees another chip's rows.  The pack is compiled once per bucket and
    segment layout (``EXEC_CACHE``), so an NRT refresh that keeps both
    reuses it."""
    cache = EXEC_CACHE if cache is None else cache
    axes = tuple(mesh.axis_names)
    shards = mesh.size
    chip_rows = sum(s.ann.num_docs // shards for s in segments)
    bucket = bucket_rows(chip_rows)
    n_rows = sum(s.num_docs for s in segments)
    with obs.span("packed.pack", rows=n_rows, segments=len(segments),
                  shards=shards, chip_rows=chip_rows):
        specs = [distributed.index_pspec(v, axes) for v in views]
        args = (jax.device_put(list(views), [
            jax.tree.map(lambda sp: NamedSharding(mesh, sp), spec) for spec in specs
        ]),)

        def build():
            return jax.shard_map(
                lambda vs: _packed_view(config, vs, bucket), mesh=mesh,
                in_specs=(specs,), out_specs=distributed.index_pspec(views[0], axes),
                check_vma=False,
            )

        view = cache.get(("sharded_pack", mesh, config, bucket), build, args)(*args)
        ids = _shard_ids(segments, shards, bucket)
        glive = np.concatenate([s.live for s in segments])
        live = (ids >= 0) & glive[np.maximum(ids, 0)]
        doc = NamedSharding(mesh, P(axes))
        return PackedSegments(
            view=view, bucket=bucket, n_rows=n_rows,
            n_live=sum(s.num_live for s in segments),
            live=jax.device_put(live.reshape(-1), doc),
            any_deleted=any(s.del_count for s in segments),
            seg_names=tuple(s.name for s in segments),
            seg_rows=tuple(s.num_docs for s in segments),
            mesh=mesh, ids=jax.device_put(ids.reshape(-1), doc),
            # Host numpy: the bitmap is built here before its device_put.
            shard_live=tuple(live.sum(axis=1).tolist()),  # reprolint: disable=hostsync
        )


# --------------------------------------------------------------------------
# Blockmax over the packed view
# --------------------------------------------------------------------------


def packed_blockmax(pk: PackedSegments, config, block_size: int):
    """A BlockMaxIndex over the packed view (the monolithic builder applies
    unchanged — the packed view IS a monolithic index).  Pad/deleted rows
    may inflate stage-1 bounds (optimistic = admissible); stage 2 masks
    them through the live bitmap.  Cached per block size on the snapshot."""
    bm = pk.bm_cache.get(block_size)
    if bm is None:
        from repro.core import blockmax

        bm = blockmax.build_blockmax(
            pk.view, block_size,
            signed_store=getattr(config, "signed_store", False),
        )
        pk.bm_cache[block_size] = bm
    return bm


# --------------------------------------------------------------------------
# The single-launch search
# --------------------------------------------------------------------------


def _pad_mask_cols(fm: jax.Array, bucket: int) -> jax.Array:
    """Pad a (n_rows,) / (B, n_rows) predicate bitmap with zeros to the
    bucket width (pad rows are never keepable)."""
    pad = bucket - fm.shape[-1]
    if pad == 0:
        return fm != 0
    zeros = jnp.zeros(fm.shape[:-1] + (pad,), bool)
    return jnp.concatenate([fm != 0, zeros], axis=-1)


def packed_search(
    pk: PackedSegments,
    pipeline,
    matcher,
    q_norm: jax.Array,
    k: int,
    depth: int,
    rerank: bool,
    quantized: bool,
    use_kernel: Optional[bool],
    fm: Optional[jax.Array] = None,
    static_rows: bool = False,
    n_keep: Optional[int] = None,
    bm=None,
    cache: Optional[ExecutableCache] = None,
) -> Tuple[jax.Array, jax.Array]:
    """ONE compiled launch for the whole segmented snapshot (across the
    chips for a doc-sharded one, :func:`_sharded_search`).

    Mask selection (cheapest exact option first):
      * ``pk.full`` and no predicate — no mask at all: the exact unfiltered
        monolithic call graph.
      * no deletes, no predicate, ``static_rows=True`` — the kernels'
        static ``n_docs`` ragged-row bound (no bitmap streamed; executable
        keys on n_rows, so this is for shape-static callers like benches).
      * otherwise — liveDocs ∧ row-validity [∧ predicate] composed into the
        kernels' ``filt`` operand: dynamic content, static shape, so NRT
        refresh cycles never recompile.

    ``k``/``depth`` are the caller's logical knobs; output is
    (scores (B, k_out), ids (B, k_out)) with ``k_out = min(k, depth,
    live docs)`` — exactly the per-segment loop's output width.
    """
    cache = EXEC_CACHE if cache is None else cache
    bucket = pk.bucket
    d_eff = min(depth, pk.n_live)
    k_out = min(k, d_eff)
    if k_out <= 0:
        raise ValueError("packed search over zero live docs")
    if pk.mesh is not None:
        if static_rows or n_keep is not None:
            raise ValueError(
                "a doc-sharded packed view takes no static_rows or blockmax"
            )
        return _sharded_search(
            pk, pipeline, matcher, q_norm, k_out, d_eff, rerank, quantized,
            use_kernel, fm, cache,
        )
    q_rep = pipeline.encoder(pk.view, q_norm)

    use_filt = (fm is not None) or pk.any_deleted or (
        pk.n_rows < bucket and not static_rows
    )
    n_docs = None
    if not use_filt and pk.n_rows < bucket:
        n_docs = pk.n_rows  # static_rows: kernel-side ragged bound
    fm_arg = None
    if fm is not None:
        fm_arg = _pad_mask_cols(jnp.asarray(fm), bucket)

    def build():
        # Named for the trace: the executable's module is jit_packed_search.
        def packed_search(view, live, fm_in, q_rep_in, q_norm_in, bm_in):
            from repro.core import pipeline as pl

            filt = None
            if use_filt:
                filt = live if fm_in is None else (
                    fm_in & (live if fm_in.ndim == 1 else live[None, :])
                )
            if n_keep is not None:
                keep = min(n_keep, bm_in.num_blocks)
                s, i = pl.BlockMaxMatcher(n_keep=keep)(
                    view, q_rep_in, depth, bm=bm_in,
                    use_kernel=use_kernel, filt=filt,
                )
            else:
                s, i = matcher(
                    view, q_rep_in, depth, use_kernel=use_kernel,
                    filt=filt, n_docs=n_docs,
                )
            if rerank:
                rs = pl.candidate_scores(view, q_norm_in, i, quantized)
                out_s, pos = jax.lax.top_k(rs, k_out)
                return out_s, jnp.take_along_axis(i, pos, axis=-1)
            return s[:, :k_out], i[:, :k_out]
        return packed_search

    args = (pk.view, pk.live, fm_arg, q_rep, q_norm, bm)
    key = (
        "search", matcher, depth, k_out, rerank, quantized, use_kernel,
        use_filt, n_docs, n_keep,
    )
    exe = cache.get(key, build, args)
    pk.search_temp_bytes = cache.temp_bytes(exe)
    return exe(*args)


def sharded_search_fn(
    mesh, view_spec, matcher, depth: int, k: int, rerank: bool,
    quantized: bool, use_kernel: bool, filtered: bool = False,
):
    """``fn(view, live, ids, fm, q_rep, q_norm) -> (scores, ids)`` over
    per-chip packed views: each chip runs the match stage over its own rows
    with its live bitmap (AND the predicate ``fm``, looked up through the
    chip's global ids, when ``filtered``), maps its local rows to global
    doc ids through ``ids``, and ``distributed.merge_global_depth`` merges.
    Every per-doc operand is doc-sharded; queries and predicate are
    replicated."""
    axes = tuple(mesh.axis_names)

    # Named for the trace: the executable's module is jit_sharded_packed_search.
    def sharded_packed_search(view, live, ids, fm, q_rep, q_norm):
        filt = live
        if filtered:
            # Pad rows (id -1) are dead in ``live`` already.
            filt = live & jnp.take(fm, jnp.maximum(ids, 0), axis=-1)
        loc_s, loc_i = matcher(view, q_rep, depth, use_kernel=use_kernel, filt=filt)
        gid = jnp.where(loc_i >= 0, ids[jnp.maximum(loc_i, 0)], -1)
        return distributed.merge_global_depth(
            view, loc_s, loc_i, gid, q_norm, axes, k, depth, rerank, quantized
        )

    return jax.shard_map(
        sharded_packed_search, mesh=mesh,
        in_specs=(view_spec, P(axes), P(axes), P(), P(), P()),
        out_specs=(P(), P()), check_vma=False,
    )


def _sharded_search(
    pk: PackedSegments, pipeline, matcher, q_norm: jax.Array, k_out: int,
    d_eff: int, rerank: bool, quantized: bool, use_kernel: Optional[bool],
    fm: Optional[jax.Array], cache: ExecutableCache,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`packed_search` over a doc-sharded packed view: one compiled
    launch across the chips (:func:`sharded_search_fn`)."""
    from repro.kernels.fused_topk import ops as fused

    mesh = pk.mesh
    uk = fused.resolve_use_kernel(use_kernel)
    rep = NamedSharding(mesh, P())
    q_rep = jax.device_put(pipeline.encoder(pk.view, q_norm), rep)
    q_norm = jax.device_put(q_norm, rep)
    fm_arg = None
    if fm is not None:
        # Global ids run below shards * bucket: one static width a bucket.
        fm_arg = jax.device_put(
            _pad_mask_cols(jnp.asarray(fm), pk.shards * pk.bucket), rep
        )

    def build():
        return sharded_search_fn(
            mesh, distributed.index_pspec(pk.view, mesh.axis_names), matcher,
            d_eff, k_out, rerank, quantized, uk, filtered=fm is not None,
        )

    args = (pk.view, pk.live, pk.ids, fm_arg, q_rep, q_norm)
    key = ("sharded_search", mesh, matcher, d_eff, k_out, rerank, quantized, uk)
    exe = cache.get(key, build, args)
    pk.search_temp_bytes = cache.temp_bytes(exe)
    with obs.span("shard.merge", shards=pk.shards,
                  bytes=distributed.merge_bytes(q_norm.shape[0], d_eff,
                                                pk.shards, rerank)):
        return exe(*args)
