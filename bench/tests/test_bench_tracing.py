"""The reduction from a device trace to busy time, idle share, kernel time
and the breakdown, on hand-made events and on a small recorded chip trace."""
import json
import os

import pytest

from bench.tests import tiny
from bench.lib import tracing

RECORDED = os.path.join(tiny.ROOT, "bench", "tests", "data", "trace_fw_bulk.json")


def ev(plane, line, name, start, dur, module=None):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(start),
            "dur_ns": float(dur), "module": module}


D0, D1 = "/device:TPU:0", "/device:TPU:1"
KERNEL = ('%fused_topk.1 = (f32[256,128]{1,0}, s32[256,128]{1,0}) custom-call(bf16[256,1024]{1,0} '
          '%pad.4, bf16[1572864,1024]{1,0} %pad.5), custom_call_target="tpu_custom_call"')


@pytest.fixture
def events():
    return [
        ev("/host:CPU", "python", "bench.window", 100, 1000),
        ev("/host:CPU", "python", "bench.request", 100, 500),
        ev("/host:CPU", "python", "bench.request", 650, 400),
        ev(D0, "XLA Modules", "jit_search(1)", 150, 400),
        ev(D0, "XLA Ops", KERNEL, 150, 300),
        ev(D0, "XLA Ops", "gather", 400, 100),         # overlaps the kernel
        ev(D0, "XLA Ops", KERNEL, 700, 200),
        ev(D0, "XLA Ops", "copy", 50, 100),            # half before the window
        ev(D1, "XLA Ops", KERNEL, 200, 100),
    ]


def test_union_merges_overlaps():
    assert tracing.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tracing.union_ns([]) == 0
    assert tracing.union_ns([(0, 10), (2, 3)]) == 10


def test_busy_and_idle_are_clipped_to_the_window(events):
    # D0: [100,150) of the copy + [150,500) + [700,900) = 50 + 350 + 200 = 600
    # D1: 100.  Mean over the two devices: 350 of a 1000 ns window.
    assert tracing.busy_ns(events) == pytest.approx(350.0)
    assert tracing.idle_pct(events) == pytest.approx(65.0)


def test_kernel_ops_and_their_modules(events):
    ops = tracing.ops_matching(events, work_pattern())
    assert sum(o["dur_ns"] for o in ops) == 600
    mods = tracing.modules_containing(events, work_pattern())
    assert [m["name"] for m in mods] == ["jit_search(1)"]


def test_breakdown(events):
    top = tracing.top_ops(events, 2)
    assert top[0][0] == "fused_topk.1 custom-call" and top[0][1] == pytest.approx(300e-9)
    gaps = tracing.idle_gaps(events, 3)
    # D0 idles over [500, 700) and [900, 1100); the longest is named by the
    # request span open at its midpoint.
    assert gaps[0][1] == pytest.approx(200e-9)
    assert {g[0] for g in gaps[:2]} <= {"bench.request", "no bench span"}


def test_no_window_span_is_an_error(events):
    with pytest.raises(ValueError):
        tracing.idle_pct([e for e in events if e["name"] != "bench.window"])


def test_recorded_chip_trace():
    """The first 0.65 s of a traced glove-fw.bulk window (TPU v5 lite): four
    launches of the search executable, each holding one kernel op."""
    with open(RECORDED) as f:
        events = json.load(f)
    lo, hi = tracing.window(events)
    busy = tracing.busy_ns(events)
    assert 0 < busy <= hi - lo
    assert 0 <= tracing.idle_pct(events) < 20
    ops = tracing.ops_matching(events, work_pattern())
    mods = tracing.modules_containing(events, work_pattern())
    assert len(ops) == len(mods) == 4
    for o, m in zip(sorted(ops, key=lambda e: e["start_ns"]), sorted(mods, key=lambda e: e["start_ns"])):
        assert m["start_ns"] <= o["start_ns"] and o["dur_ns"] < m["dur_ns"]
    assert sum(o["dur_ns"] for o in ops) < busy
    assert tracing.top_ops(events, 1)[0][0] == "fused_topk.1 custom-call"


def work_pattern():
    from bench.lib import registry

    return registry.load_module(os.path.join(tiny.ROOT, "bench", "work", "fused_topk.py")).TRACE_NAME
