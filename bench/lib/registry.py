"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

A cell names a configuration and a traffic mix; both are data files found by
name.  The per-layer metrics a cell reports are the ``per_layer`` entries that
list the cell (or list none and move an end-to-end metric the cell reports).
Each metric's reader, each traffic generator, each kernel's work function and
each configuration's plain reference is a module loaded from its file, so
adding one is adding a file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import one file as a module (metric names hold dots, so these files
    are loaded by path, never through ``import``)."""
    name = "bench_dyn_" + os.path.relpath(path, ROOT).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(cell: str, root: str = ROOT) -> Cell:
    """The cell named ``cell`` with its configuration and traffic loaded."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if cell not in by_name:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    w = by_name[cell]
    bdir = os.path.join(root, "bench")
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return Cell(
        name=cell,
        chips=int(w["chips"]),
        config=load_json(os.path.join(bdir, "configs", w["config"] + ".json")),
        traffic=load_json(os.path.join(bdir, "traffic", w["traffic"] + ".json")),
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def metric_reader(cell: Cell, name: str) -> ModuleType:
    """``bench/metrics/<name>.py``; a metric split by cells
    (``idle_pct.bulk``) without a file of its own reads its base quantity's
    (``idle_pct.py``)."""
    mdir = os.path.join(cell.root, "bench", "metrics")
    path = os.path.join(mdir, name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(mdir, name.split(".")[0] + ".py")
    return load_module(path)


def work_module(cell: Cell, kernel: str) -> ModuleType:
    return load_module(os.path.join(cell.root, "bench", "work", kernel + ".py"))


def reference_module(cell: Cell) -> ModuleType:
    ref = cell.config["reference"]
    return load_module(os.path.join(cell.root, "bench", "references", ref + ".py"))


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]
