"""The program's own spans in a profiler capture, and the numbers they give.

The program opens spans at the boundaries of its serving path
(``repro.obs.span``): ``ann.*`` in ``AnnService``, ``packed.*`` in the packed
search, ``writer.*`` in ``IndexWriter``.  :func:`program_events` reads them
from a capture, each with its ids (``args``) and the host thread it ran on
(``thread``: the profiler names every Python thread's line alike, so lines
are told apart by position).  Every reduction of ``bench.lib.tracing``
selects by plane, line or the ``bench.`` prefix, so it reads the same values
with them in the list.

:func:`traced_run` is ``harness.run`` traced, with these spans and the
program's two queue counters let through to the readers below
(``bench/spans.py``).
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple
from unittest import mock

import numpy as np

from bench.lib import harness, registry, tracing
from bench.lib.tracing import DEVICE_PLANE, Event

PROGRAM_PREFIXES = ("ann.", "packed.", "writer.")
NO_SPAN = "no program span"
SPAN_NAMES = ("ann.enqueue", "ann.queue_wait", "ann.coalesce", "ann.launch", "ann.dispatch",
              "ann.handoff", "ann.resolve", "packed.compile", "packed.pack", "packed.append",
              "writer.flush", "writer.refresh")


def program_events(log_dir: str) -> List[Event]:
    """The program's spans in a capture, as events with ``thread`` and ``args``."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    out: List[Event] = []
    for p, path in enumerate(paths):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if DEVICE_PLANE.match(plane.name):
                continue
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES):
                        out.append({
                            "plane": plane.name, "line": line.name, "name": ev.name,
                            "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns),
                            "thread": f"{p}/{li}", "args": {k: v for k, v in ev.stats},
                        })
    return out


def load_events(log_dir: str) -> List[Event]:
    """``tracing.load_events`` plus the program's spans."""
    return tracing.load_events(log_dir) + program_events(log_dir)


def program_counters(svc) -> Dict[str, Any]:
    """``AnnService``'s queue counters, beside ``harness.counters``."""
    return {"queue_wait_s": svc.queue_wait_s, "async_requests": svc.async_requests}


def is_program(e: Event) -> bool:
    return "thread" in e


def spans(events: Sequence[Event], name: str) -> List[Event]:
    """The ``name`` spans that start inside the window."""
    lo, hi = tracing.window(events)
    return [e for e in events if is_program(e) and e["name"] == name and lo <= e["start_ns"] < hi]


def mean_ms(events: Sequence[Event], name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans that start inside the window."""
    got = spans(events, name)
    return sum(e["dur_ns"] for e in got) / len(got) / 1e6 if got else None


def dispatch_ms(events: Sequence[Event]) -> Optional[float]:
    """Host time from a launch's start until its search executable has been
    called: padding, host-to-device copies, normalising and encoding the
    queries, the executable-cache lookup and the call."""
    return mean_ms(events, "ann.dispatch")


def _idle_under(events: Sequence[Event], covered: Sequence[Event]) -> Optional[float]:
    """Nanoseconds of the window, averaged over the devices, in which one of
    ``covered`` is open and no device op runs."""
    lo, hi = tracing.window(events)
    planes = tracing.device_planes(events)
    if not planes or hi <= lo:
        return None
    cov = tracing._clip([(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in covered], lo, hi)
    total = 0.0
    for plane in planes:
        ops = tracing._clip(tracing.op_intervals(events, plane), lo, hi)
        total += tracing.union_ns(cov + ops) - tracing.union_ns(ops)
    return total / len(planes)


def idle_under_pct(events: Sequence[Event], name: Optional[str] = None) -> Optional[float]:
    """Share of the window, in percent, in which a program span (``name``,
    or any) is open on some host line and no device op runs."""
    lo, hi = tracing.window(events)
    covered = [e for e in events if is_program(e) and (name is None or e["name"] == name)]
    idle = _idle_under(events, covered)
    return None if idle is None else 100.0 * idle / (hi - lo)


def idle_in_launch_pct(events: Sequence[Event]) -> Optional[float]:
    """Share of the window in which an ``ann.launch`` span is open and the
    device idles, in percent: the idle that the launch path itself leaves
    (dispatch, hand-off), as against waiting for work."""
    return idle_under_pct(events, "ann.launch")


def idle_under_program_pct(events: Sequence[Event]) -> Optional[float]:
    """Share of the device's idle time in the window that falls under some
    program span on some host line, in percent."""
    under, idle = idle_under_pct(events), tracing.idle_pct(events)
    return None if under is None or not idle else 100.0 * under / idle


def innermost(events: Sequence[Event], t: float) -> str:
    """The program span open at ``t`` that started last, on any host line."""
    open_ = [e for e in events
             if is_program(e) and e["start_ns"] <= t < e["start_ns"] + e["dur_ns"]]
    if not open_:
        return NO_SPAN
    return max(open_, key=lambda e: (e["start_ns"], -e["dur_ns"]))["name"]


def device_gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of every stretch of the window in which the first
    device runs no op, longest first."""
    lo, hi = tracing.window(events)
    planes = tracing.device_planes(events)
    if not planes:
        return []
    gaps, cur = [], lo
    for a, b in sorted(tracing._clip(tracing.op_intervals(events, planes[0]), lo, hi)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def idle_gaps_program(events: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """The ``n`` longest device gaps in the window, in seconds, each named by
    the innermost program span open at its midpoint (``no program span``
    where none is)."""
    return [[innermost(events, (a + b) / 2), (b - a) / 1e9] for a, b in device_gaps(events)[:n]]


def queue_wait_ms(counters: Dict[str, Any]) -> Optional[float]:
    """Mean wait of an async request from its enqueue to the start of its
    launch, from ``AnnService``'s counter deltas over the window."""
    n = counters.get("async_requests")
    return 1e3 * counters["queue_wait_s"] / n if n else None


def readings(ctx: harness.MetricContext, win: harness.Window, loop: str) -> Dict[str, Any]:
    """What the program's spans and counters give in a traced window of a
    ``closed`` or ``open`` loop."""
    events = ctx.events
    split = ".bulk" if loop == "closed" else ".poisson"
    new = {"dispatch_ms" + split: dispatch_ms(events),
           "idle_in_launch_pct" + split: idle_in_launch_pct(events)}
    out: Dict[str, Any] = {"new_per_layer": new}
    if loop == "open":
        new["queue_wait_ms"] = queue_wait_ms(ctx.counters)
        ok = np.isfinite(win.done)
        # Request latency less the generator's lateness, against what the
        # program's own spans and counters add up to.
        out["sent_to_done_ms"] = float(np.mean(win.done[ok] - win.sent[ok]) * 1e3)
        parts = [new["queue_wait_ms"], mean_ms(events, "ann.launch"),
                 mean_ms(events, "ann.resolve")]
        out["queue_wait_launch_resolve_ms"] = None if None in parts else sum(parts)
    narrow = [e for e in events if not is_program(e)]
    out.update(
        per_layer_without_program_spans={
            k: v["value"] for k, v in harness.per_layer(
                ctx.cell, harness.MetricContext(ctx.cell, narrow, ctx.counters, ctx.peaks)).items()
        },
        idle_under_program_pct=idle_under_program_pct(events),
        idle_under_span_pct={n: idle_under_pct(events, n) for n in SPAN_NAMES},
        span_mean_ms={n: mean_ms(events, n) for n in SPAN_NAMES},
        span_count={n: len(spans(events, n)) for n in SPAN_NAMES},
        idle_gaps_program=idle_gaps_program(events),
        counters=ctx.counters,
    )
    return out


def traced_run(cell: registry.Cell, seed: int, seconds: float, t_start: float,
               out_dir: Optional[str] = None) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``harness.run`` with ``--trace 1``, its loader and counters widened to
    the program's spans and queue counters for the length of the call; the
    harness's result and :func:`readings`.  ``out_dir`` receives the raw
    capture."""
    narrow_load, narrow_counters, narrow_per_layer = (
        tracing.load_events, harness.counters, harness.per_layer)
    seen: Dict[str, Any] = {}

    def load(log_dir):
        if out_dir:
            shutil.copytree(log_dir, os.path.join(out_dir, f"{cell.name}.{seed}"),
                            dirs_exist_ok=True)
        return narrow_load(log_dir) + program_events(log_dir)

    def counters(svc, cc):
        return dict(narrow_counters(svc, cc), **program_counters(svc))

    def per_layer(cell_, ctx):
        seen["ctx"] = ctx
        return narrow_per_layer(cell_, ctx)

    def keep(run, loop):
        def kept(*a, **kw):
            seen["loop"], seen["win"] = loop, run(*a, **kw)
            return seen["win"]
        return kept

    with mock.patch.object(tracing, "load_events", load), \
            mock.patch.object(harness, "counters", counters), \
            mock.patch.object(harness, "per_layer", per_layer), \
            mock.patch.object(harness, "run_closed", keep(harness.run_closed, "closed")), \
            mock.patch.object(harness, "run_open", keep(harness.run_open, "open")):
        result = harness.run(cell, seed, seconds, True, t_start)
    return result, readings(seen["ctx"], seen["win"], seen["loop"])


def span_cost_ns(n: int = 20000) -> Dict[str, float]:
    """Nanoseconds one empty ``repro.obs.span`` with two ids adds, with the
    profiler off and inside a capture (an empty loop's cost taken out)."""
    import tempfile

    from repro import obs

    def per_iter(with_span: bool) -> float:
        t0 = time.perf_counter_ns()
        for i in range(n):
            if with_span:
                with obs.span("ann.cost", launch=i, rows=n):
                    pass
        return (time.perf_counter_ns() - t0) / n

    bare = per_iter(False)
    off = per_iter(True) - bare
    tdir = tempfile.mkdtemp(prefix="bench_spans_cost_")
    try:
        with tracing.capture(tdir):
            on = per_iter(True) - bare
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return {"off": off, "on": on, "empty_loop": bare}
