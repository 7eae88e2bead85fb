"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
the metrics; each of them is a file of its own, found by that name:

* a configuration: the ``file`` its entry names (``configs/<name>.json``);
* a traffic mix: ``traffic/<name>.json``;
* a metric: ``metrics/<name>.py``, a reader with ``read(run)`` that
  returns a number, or None when the run holds nothing to read.

Adding a configuration, a mix or a metric therefore means adding files
and entries, never editing the harness (``tests/test_discovery.py``).
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

from . import cluster

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    def __init__(self, root: str, spec: Dict, name: str,
                 bench_dir: str = BENCH_DIR):
        self.root = root
        self.spec = spec
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        self.entry = by_name[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in spec["configs"]}[
            self.entry["config"]]
        with open(os.path.join(root, cfg_entry["file"])) as fh:
            self.config = json.load(fh)
        with open(os.path.join(bench_dir, "traffic",
                               self.entry["traffic"] + ".json")) as fh:
            self.traffic = json.load(fh)
        # a gangs block this harness cannot run fails here, at load
        cluster.validate_gangs(self.config, self.traffic)
        self.bench_dir = bench_dir

    def metrics(self, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]


def load_cell(root: str, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    return Cell(root, spec, name, bench_dir)


def reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "metrics", name + ".py")
    mod_name = "benchmark_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(cell: Cell, kind: str, run) -> Dict[str, Dict]:
    """``{name: {"value", "unit"}}`` for every metric of ``kind`` whose
    reader found something to read."""
    out = {}
    for m in cell.metrics(kind):
        value: Optional[float] = reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
