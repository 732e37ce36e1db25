"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric, one adapter or one reader is a file of its own under
``<root>/benchmark/``; a later PR adds files and entries and edits nothing
that is there:

    configs/<config>.json        sizes, source, reduced, assumed, adapter
    traffic/<traffic>.json       the mix's parameters, read by traffic.py
    adapters/<adapter>.py        drives the system under test for a family
    layer_metrics/<metric>.json  one per-layer metric: reader + parameters
    readers/<reader>.py          takes a number from trace, spans, counters

``root`` is the checkout; tests pass a temporary one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCHMARK_FILE = "BENCHMARK.json"
BENCH_DIR = "benchmark"


class CellError(ValueError):
    """The cell, or a file it names, is not there or not well formed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{path} does not exist") from None
    except json.JSONDecodeError as e:
        raise CellError(f"{path} is not JSON: {e}") from None


def load_module(root: str, kind: str, name: str):
    """Import ``<root>/benchmark/<kind>/<name>.py`` by its path."""
    path = os.path.join(root, BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]      # the BENCHMARK.json entries this cell reports
    per_layer: list[dict]       # likewise, each with its layer-metric file
    root: str = field(repr=False, default=".")

    @property
    def adapter_name(self) -> str:
        return self.config["adapter"]


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    bench = _load_json(os.path.join(root, BENCHMARK_FILE))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise CellError(f"no workload {workload!r} in {BENCHMARK_FILE} "
                        f"(it has: {names})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise CellError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which {BENCHMARK_FILE} lacks")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(root, BENCH_DIR, "traffic",
                                      f"{entry['traffic']}.json"))
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in bench["per_layer"]:
        if not _reports(m, workload) or m["moves"] not in reported:
            continue
        spec = _load_json(os.path.join(root, BENCH_DIR, "layer_metrics",
                                       f"{m['name']}.json"))
        if config["adapter"] in spec.get("adapters", [config["adapter"]]):
            per_layer.append({**m, "spec": spec})
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=end_to_end, per_layer=per_layer,
                root=root)
