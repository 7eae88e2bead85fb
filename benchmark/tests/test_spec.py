"""BENCHMARK.json names only parts that exist, in the contract's form."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = os.path.join(ROOT, "benchmark")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_every_part_is_a_file_found_by_name():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert json.load(fh)["name"] == c["name"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))


def test_names_units_and_what_each_cell_reports():
    spec = _spec()
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    names = cells + [c["name"] for c in spec["configs"]] + list(e2e) + [
        m["name"] for m in spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              cells))
    assert "workloads" not in e2e["setup_s"]
    for cell in cells:
        reports = [n for n, m in e2e.items()
                   if cell in m.get("workloads", cells)]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(cell in m["workloads"] for m in spec["per_layer"])
