"""The four-chip cell ``w2v-fw.sharded4``: it resolves to its files, its
per-layer readers read a trace with four chip planes, and whole runs of it
at a tiny size on four virtual CPU devices are correct, while a planted
fault and the precision control are not."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny
from bench.lib import harness, registry

CELL = "w2v-fw.sharded4"
PLANES = [f"/device:TPU:{n}" for n in range(4)]
KERNEL = ('%fused_topk.1 = (f32[256,128]{1,0}, s32[256,128]{1,0}) custom-call(bf16[256,1024]{1,0} '
          '%pad.4, bf16[786432,1024]{1,0} %param.6), custom_call_target="tpu_custom_call"')
GATHER = ('%all-gather.10 = f32[4,100,256]{2,1,0:T(8,128)S(1)} all-gather(%copy.29), channel_id=1, '
          'replica_groups={{0,1,2,3}}, dimensions={0}')
PMAX = ('%pmax.7 = f32[256,100]{1,0:T(8,128)S(1)} all-reduce(%compare_select_fusion.1), '
        'channel_id=1, replica_groups={{0,1,2,3}}')
# Reads an all-gather's result: not a collective itself.
NEG = '%neg.5 = f32[4,100,256]{2,1,0:T(8,128)S(1)} negate(%all-gather.10)'


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(start),
            "dur_ns": float(dur)}


@pytest.fixture
def cell():
    return registry.resolve(CELL)


def trace(kernel_ns):
    """Two launches on each of four chips: the search module, its kernel,
    two all-gathers and an all-reduce; the window is 10 ms."""
    events = [ev("/host:CPU", "python", "bench.window", 0, 10e6)]
    for plane, k in zip(PLANES, kernel_ns):
        for start in (1e6, 5e6):
            events += [
                ev(plane, "XLA Modules", "jit_sharded_packed_search(1)", start, 3e6),
                ev(plane, "XLA Ops", KERNEL, start, k),
                ev(plane, "XLA Ops", GATHER, start + k, 20e3),
                ev(plane, "XLA Ops", GATHER.replace("f32", "s32"), start + k + 20e3, 20e3),
                ev(plane, "XLA Ops", NEG, start + k + 40e3, 5e3),
                ev(plane, "XLA Ops", PMAX, start + k + 50e3, 10e3),
            ]
    return events


def ctx(cell, events):
    return harness.MetricContext(
        cell=cell, events=events, counters={"batches": 2, "queries": 512},
        peaks=registry.peaks("TPU v5 lite"),
    )


def read(cell, name, events):
    return registry.metric_reader(cell, name).read(ctx(cell, events))


def test_cell_resolves_four_chips_and_its_metrics(cell):
    assert cell.chips == 4 and cell.config["name"] == "ann-word2vec-fw"
    assert cell.config["writer"]["shards"] == 4 and cell.config["reference"] == "fakewords_classic"
    assert cell.traffic["generator"] == "closed" and cell.traffic["batch"] == 256
    assert {m["name"] for m in cell.end_to_end} == {
        "qps.bulk", "p50_ms.bulk", "p95_ms.bulk", "recall_at_10", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [
        "search_ms.sharded4", "idle_pct.sharded4", "shard_topk_roofline_pct", "merge_ms",
        "shard_skew_pct", "compiles_in_window.sharded4"]
    assert registry.metric_reader(cell, "search_ms.sharded4").__file__.endswith("search_ms.py")
    assert registry.metric_reader(cell, "idle_pct.sharded4").__file__.endswith("idle_pct.py")
    assert registry.metric_reader(
        cell, "compiles_in_window.sharded4").__file__.endswith("compiles_in_window.py")


def test_search_and_idle_average_over_the_chips(cell):
    events = trace([2e6] * 4)
    assert read(cell, "search_ms.sharded4", events) == pytest.approx(3.0)
    # Each chip is busy 2 x (2 ms + 55 us) of the 10 ms window.
    assert read(cell, "idle_pct.sharded4", events) == pytest.approx(100 * (1 - 4.11e6 / 10e6))


def test_merge_ms_counts_collectives_only(cell):
    # 20 + 20 + 10 us a launch on each chip; the negate of the gathered
    # scores is not a collective.
    assert read(cell, "merge_ms", trace([2e6] * 4)) == pytest.approx(0.05)
    one_chip = [e for e in trace([2e6] * 4) if "all-" not in e["name"].split(" = ")[-1][:40]]
    assert read(cell, "merge_ms", one_chip) is None


def test_skew_is_the_slowest_chip_over_the_mean(cell):
    assert read(cell, "shard_skew_pct", trace([2e6] * 4)) == pytest.approx(0.0)
    assert read(cell, "shard_skew_pct", trace([2e6, 2e6, 2e6, 2.4e6])) == pytest.approx(
        100 * (2.4 / 2.1 - 1))
    assert read(cell, "shard_skew_pct", [e for e in trace([2e6] * 4)
                                         if e["plane"] in ("/host:CPU", PLANES[0])]) is None


def test_shard_roofline_counts_each_chips_share(cell):
    events = trace([2e6] * 4)
    share = read(cell, "shard_topk_roofline_pct", events)
    # B = 256 is compute-bound: the least time of 749,952 rows x 600 columns.
    least = 2.0 * 256 * (2999808 // 4) * 600 / 197e12
    assert share == pytest.approx(100 * least / 2e-3)
    # The one-chip reader counts all rows on every chip: four times high.
    whole = registry.load_module(os.path.join(cell.root, "bench", "metrics", "topk_roofline_pct.py"))
    assert whole.read(ctx(cell, events)) == pytest.approx(4 * share)
    assert 0 < share < 100


RUNS = r"""
import json, os, sys, tempfile, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [%(root)r, os.path.join(%(root)r, "src")]
from bench.tests import tiny
from bench.lib import harness, registry
cell = registry.resolve("w2v-fw.sharded4", tiny.make_root(tempfile.mkdtemp()))
out = {}
for name, kw in (("sound", {}), ("alter", {"fault": "alter"}), ("control", {"control": True})):
    out[name] = harness.run(cell, 2**33 + 5, 1.0, False, time.perf_counter(), **kw)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", RUNS % {"root": tiny.ROOT}], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_run_is_correct(runs):
    res = runs["sound"]
    assert res["correct"], res["check"]
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"qps.bulk", "p50_ms.bulk", "p95_ms.bulk", "recall_at_10",
                                   "setup_s"}


def test_sharded_fault_is_not_correct(runs):
    assert not runs["alter"]["correct"], runs["alter"]["check"]


def test_sharded_control_is_not_correct(runs):
    assert not runs["control"]["correct"], runs["control"]["check"]
