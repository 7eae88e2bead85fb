"""The benchmark's own tests run on the CPU, at sizes a test can hold."""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def tiny(cell, nodes=24, backlog=None, init_pods=10):
    """The cell cut to a test's size: fewer nodes and pods, the same
    distributions, traffic and checks."""
    cell.config = copy.deepcopy(cell.config)
    cell.config["nodes"]["count"] = nodes
    if "init_pods" in cell.config:
        cell.config["init_pods"] = init_pods
    cell.traffic = dict(cell.traffic)
    if backlog is not None:
        cell.traffic["backlog_pods"] = backlog
    return cell


# cells the harness can run that BENCHMARK.json does not hold (yet):
# name -> (configuration, traffic mix).  ``k8s5k.prescore`` left the
# benchmark for its spread on the driver's one-chip hosts (PERF.md);
# its mix, readers and check stay tested here.
UNLISTED = {"k8s5k.prescore": ("k8s5k", "prescore")}


@pytest.fixture
def tiny_cell():
    import json

    import koordinator_tpu  # noqa: F401  (x64, as run.py imports it)
    from benchmark.harness.spec import Cell

    def make(name, **kw):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            spec = json.load(fh)
        if name in UNLISTED:
            config, traffic = UNLISTED[name]
            spec["workloads"].append({"name": name, "config": config,
                                      "traffic": traffic, "chips": 1})
        cell = Cell(ROOT, spec, name)
        if cell.traffic["rpc"] == "assign":
            kw.setdefault("backlog", 96)
        return tiny(cell, **kw)

    return make
