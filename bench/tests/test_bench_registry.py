"""Cells, traffic mixes and metrics are found by name, so adding one is
adding files; and the run refuses to measure without a chip."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.tests import tiny
from bench.lib import loadgen, registry


def test_every_declared_cell_resolves():
    bench = registry.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = registry.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert hasattr(registry.metric_reader(cell, m["name"]), "read")
        assert hasattr(registry.reference_module(cell), "pair_scores")
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(tiny.ROOT, c["file"]))


def test_new_cell_and_metric_are_files_alone(tmp_path):
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "traffic", "trickle.json"), "w") as f:
        json.dump({"generator": "poisson", "rate_qps": 5, "pool": 8, "draw": "uniform"}, f)
    with open(os.path.join(root, "bench", "metrics", "answered_share.py"), "w") as f:
        f.write("def read(ctx):\n    return 1.0\n")
    path = os.path.join(root, "BENCHMARK.json")
    bench = registry.load_json(path)
    bench["workloads"].append({"name": "glove-fw.trickle", "config": "ann-glove-fw",
                               "traffic": "trickle", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({"name": "answered_share", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "device",
                               "moves": "setup_s", "workloads": ["glove-fw.trickle"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = registry.resolve("glove-fw.trickle", root)
    assert cell.traffic["rate_qps"] == 5 and cell.config["name"] == "ann-glove-fw"
    assert [m["name"] for m in cell.per_layer] == ["answered_share"]
    assert registry.metric_reader(cell, "answered_share").read(None) == 1.0
    with pytest.raises(KeyError):
        registry.resolve("glove-fw.absent", root)


def test_unknown_device_has_no_peaks():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("cpu")


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "glove-fw.bulk", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_without_a_chip_fails_and_prints_no_result():
    proc = _run(tiny.ROOT)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert "needs a TPU" in proc.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not proc.stdout.strip()


STEADY = '''\
"""Throwaway generator: evenly spaced single queries and one writer operation."""
import numpy as np

from bench.lib import loadgen


def plan(mix, rng, seconds):
    n = int(mix["rate_qps"] * seconds)
    return loadgen.Plan(loop="open", batch=1, pool=int(mix["pool"]),
                        picks=loadgen.picks(mix, rng), due=np.arange(n) / mix["rate_qps"],
                        ops=[(0.1, lambda svc: svc.stats())])
'''


def test_new_generator_is_a_file_alone(tmp_path):
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "generators", "steady.py"), "w") as f:
        f.write(STEADY)
    mix = {"generator": "steady", "rate_qps": 10, "pool": 8, "draw": "uniform"}
    p = loadgen.plan(mix, 2**33 + 1, 2.0, root)
    assert p.loop == "open" and len(p.due) == 20 and np.allclose(np.diff(p.due), 0.1)
    assert len(p.ops) == 1
    with pytest.raises(FileNotFoundError):
        loadgen.plan(dict(mix, generator="absent"), 1, 1.0, root)
