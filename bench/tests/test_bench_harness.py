"""Whole runs of the harness at a tiny size on the CPU, the chip check
skipped: a sound run is correct, and the precision control and each fault
this kind of cell can have come out not correct."""
import json
import os
import time

import pytest

from bench.tests import tiny  # noqa: F401  (puts src/ on the path)
from bench.lib import harness, registry


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _run(root, cell, seed, seconds=1.0, **kw):
    return harness.run(registry.resolve(cell, root), seed, seconds, False,
                       time.perf_counter(), **kw)


@pytest.mark.parametrize("cell", ["glove-fw.bulk", "glove-lsh.bulk", "glove-fw.poisson"])
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell, 2**33 + 5)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    names = {m["name"] for m in registry.resolve(cell, root).end_to_end}
    assert set(res["metrics"]) == names
    recall = [v["value"] for k, v in res["metrics"].items() if k.startswith("recall_at_10")]
    assert len(recall) == 1 and 0.0 < recall[0] <= 1.0


@pytest.mark.parametrize("cell,fault", [
    ("glove-fw.bulk", "alter"), ("glove-fw.poisson", "half"), ("glove-lsh.bulk", "alter"),
])
def test_fault_is_not_correct(root, cell, fault):
    res = _run(root, cell, 7, fault=fault)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", ["glove-fw.bulk", "glove-lsh.bulk"])
def test_control_is_not_correct(root, cell):
    """The plain reference in the program's place, one precision step below
    the stated one (``check.control_answers``)."""
    res = _run(root, cell, 11, control=True)
    assert not res["correct"], res["check"]


RAISING = '''\
from bench.lib import loadgen


def plan(mix, rng, seconds):
    def op(svc):
        raise RuntimeError("the writer operation ran")
    return loadgen.Plan(loop="closed", batch=int(mix["batch"]), pool=int(mix["pool"]),
                        picks=loadgen.picks(mix, rng), ops=[(0.0, op)])
'''


def test_writer_operations_are_carried_out(tmp_path):
    """A generator's writer operations run during the window; one that
    raises fails the run."""
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "generators", "raising.py"), "w") as f:
        f.write(RAISING)
    path = os.path.join(root, "bench", "traffic", "bulk.json")
    with open(path) as f:
        mix = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(mix, generator="raising"), f)
    with pytest.raises(RuntimeError, match="writer operations raised"):
        _run(root, "glove-fw.bulk", 3)
