"""BENCHMARK.json and the files it names, resolved by name and by nothing
else: a later PR adds a cell by adding files and entries."""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

#: the checkout: the directory that holds BENCHMARK.json and benchmark/
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")


class SpecError(Exception):
    """The benchmark's own files do not fit together."""


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    try:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        raise SpecError(f"missing file benchmark/{kind}/{name}.py") from e


def _load(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    run_seconds: int

    def readers(self) -> Dict[str, Callable]:
        """One reader per per-layer metric of this cell: ``read(run)``."""
        return {m["name"]: module("layer_metrics", m["name"]).read for m in self.per_layer}


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell_names(root: str = ROOT) -> List[str]:
    return [w["name"] for w in load_benchmark(root)["workloads"]]


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, which "
                        "BENCHMARK.json does not list")
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    here = os.path.join(root, "benchmark")
    traffic = _load(os.path.join(here, "traffic", w["traffic"] + ".json"))
    limits = _load(os.path.join(here, "limits", name + ".json"))
    if traffic["chips"] != w["chips"]:
        raise SpecError(f"workload {name!r} asks for {w['chips']} chips, its "
                        f"traffic file for {traffic['chips']}")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    cell = Cell(name=name, chips=w["chips"], config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                run_seconds=bench["run_seconds"])
    cell.readers()  # a metric with no reader is an error at start
    for kind in ("generators", "reference", "flops"):
        key = traffic["generator"] if kind == "generators" else config["family"]
        if not os.path.isfile(os.path.join(here, kind, key + ".py")):
            raise SpecError(f"missing file benchmark/{kind}/{key}.py")
    return cell
