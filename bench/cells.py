"""Find everything that belongs to a cell by the names in BENCHMARK.json.

A workload names a configuration and a traffic mix. The configuration's
file names its model module (``bench/models/<model>.py``: weights, the
program's view of them, the plain reference); the traffic mix is
``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/metrics/<name>.py``; the cell's limits are in
``bench/checks/<cell>.json``. Adding a cell, a configuration or a metric
adds files and entries, and edits none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _for_cell(entries: List[Dict], cell: str) -> List[Dict]:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def metric_reader(name: str) -> ModuleType:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(bench: Dict, name: str, root: pathlib.Path = ROOT) -> Dict:
    """Everything one run of workload ``name`` needs."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return {
        "workload": w,
        "config": cfg,
        "traffic": traffic,
        "model": importlib.import_module(f"bench.models.{cfg['model']}"),
        "end_to_end": _for_cell(bench["end_to_end"], name),
        "per_layer": _for_cell(bench["per_layer"], name),
    }
