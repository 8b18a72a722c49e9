"""Profiler capture and the reduction from a device trace to numbers.

Capture writes an ``.xplane.pb`` (``jax.profiler``); :func:`load_events`
flattens it into plain event dicts ``{plane, line, name, start_ns, dur_ns}``, keeping the device planes and the benchmark's own host spans
(``bench.*`` annotations).  Every reduction below works on that flat list,
so it is checked on a small recorded trace kept in ``bench/tests/data``.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run, and ``XLA Modules`` one per executable run.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

Event = Dict[str, Any]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Trace device activity and ``TraceAnnotation`` spans; the Python
    tracer is off so a long window stays small."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_events(log_dir: str) -> List[Event]:
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: List[Event] = []
    for path in paths:
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            device = bool(DEVICE_PLANE.match(plane.name))
            for line in plane.lines:
                for ev in line.events:
                    if not device and not ev.name.startswith("bench."):
                        continue
                    out.append({
                        "plane": plane.name, "line": line.name, "name": ev.name,
                        "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns),
                    })
    return out


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e["plane"] for e in events if DEVICE_PLANE.match(e["plane"])})


def window(events: Sequence[Event]) -> Tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's window span."""
    spans = [e for e in events if e["name"] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    s = spans[0]
    return s["start_ns"], s["start_ns"] + s["dur_ns"]


def _clip(iv: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if min(b, hi) > max(a, lo)]


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping [start, end) intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def op_intervals(events: Sequence[Event], plane: str) -> List[Tuple[float, float]]:
    return [
        (e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
        if e["plane"] == plane and e["line"] == OPS_LINE
    ]


def busy_ns(events: Sequence[Event]) -> float:
    """Device busy time inside the window: the union of each device's op
    intervals, averaged over the devices in the trace."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(union_ns(_clip(op_intervals(events, p), lo, hi)) for p in planes) / len(planes)


def idle_pct(events: Sequence[Event]) -> Optional[float]:
    lo, hi = window(events)
    if not device_planes(events) or hi <= lo:
        return None
    return 100.0 * (1.0 - busy_ns(events) / (hi - lo))


def ops_matching(events: Sequence[Event], pattern: str) -> List[Event]:
    """Device ops inside the window whose name matches ``pattern``."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    return [
        e for e in events
        if DEVICE_PLANE.match(e["plane"]) and e["line"] == OPS_LINE
        and lo <= e["start_ns"] < hi and rx.search(e["name"])
    ]


def modules_containing(events: Sequence[Event], pattern: str) -> List[Event]:
    """Executable runs (``XLA Modules`` events) inside the window that
    contain an op matching ``pattern``: the runs of the program that holds
    that kernel, whatever the executable is called."""
    lo, hi = window(events)
    ops = ops_matching(events, pattern)
    mods = [
        e for e in events
        if DEVICE_PLANE.match(e["plane"]) and e["line"] == MODULES_LINE
        and lo <= e["start_ns"] < hi
    ]
    out = []
    for m in mods:
        a, b = m["start_ns"], m["start_ns"] + m["dur_ns"]
        if any(o["plane"] == m["plane"] and a <= o["start_ns"] < b for o in ops):
            out.append(m)
    return out


def op_label(name: str) -> str:
    """A device op's HLO text shortened to its instruction name and opcode
    (``%copy.20 = f32[...] copy(...)`` -> ``copy.20 copy``)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r"\b([a-z][a-z0-9-]*)\(", rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def top_ops(events: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """The ``n`` device ops that took most time in the window, in seconds
    per device (summed over runs, averaged over devices)."""
    lo, hi = window(events)
    planes = device_planes(events)
    tot: Dict[str, float] = {}
    for e in events:
        if e["plane"] in planes and e["line"] == OPS_LINE and lo <= e["start_ns"] < hi:
            label = op_label(e["name"])
            tot[label] = tot.get(label, 0.0) + e["dur_ns"]
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / max(1, len(planes))] for name, ns in ranked]


def idle_gaps(events: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """The ``n`` longest gaps between device ops in the window on the first
    device, each named by the benchmark host span open at its midpoint."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return []
    iv = sorted(_clip(op_intervals(events, planes[0]), lo, hi))
    gaps, cur = [], lo
    for a, b in iv:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    host = [e for e in events if e["name"].startswith("bench.") and e["name"] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        names = [h["name"] for h in host if h["start_ns"] <= mid < h["start_ns"] + h["dur_ns"]]
        out.append([names[-1] if names else "no bench span", (b - a) / 1e9])
    return out
