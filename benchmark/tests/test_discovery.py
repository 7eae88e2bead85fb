"""A new configuration, traffic mix and metric are picked up from their
files and BENCHMARK.json entries alone: the dummy ones under
``tests/dummy`` run through the unchanged harness."""

import os

import pytest

from benchmark.harness.spec import load_cell, read_metrics

DUMMY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dummy")


def test_new_config_traffic_and_metric_run_from_files_alone():
    import koordinator_tpu  # noqa: F401
    from benchmark.run import run_cell

    cell = load_cell(DUMMY, "tinyclu.trickle", bench_dir=DUMMY)
    assert cell.config["nodes"]["count"] == 16
    assert cell.traffic["backlog_pods"] == 40
    res = run_cell(cell, 12345, 0.5, False, out=lambda *a, **k: None)
    assert res["correct"], res
    assert set(res["metrics"]) == {"pods_total"}
    assert res["metrics"]["pods_total"]["unit"] == "pods"
    assert res["metrics"]["pods_total"]["value"] >= 40


def test_a_reader_with_nothing_to_read_is_left_out():
    cell = load_cell(DUMMY, "tinyclu.trickle", bench_dir=DUMMY)

    class Run:
        trace = None
        records = [{"placed": 1}]

    assert read_metrics(cell, "per_layer", Run()) == {}
    assert read_metrics(cell, "end_to_end", Run()) == {
        "pods_total": {"value": 1.0, "unit": "pods"}}


def test_gang_config_runs_from_files_alone():
    """A configuration with a ``gangs`` block (``dummy/configs/
    tinygang.json``) runs through the unchanged harness: the Sync carries
    its gang table, and every cycle binds whole gangs."""
    import koordinator_tpu  # noqa: F401
    from benchmark.run import run_cell

    cell = load_cell(DUMMY, "tinygang.trickle", bench_dir=DUMMY)
    assert cell.config["gangs"]["members"] == 8
    res = run_cell(cell, 2**31 + 77, 0.5, False, out=lambda *a, **k: None)
    assert res["correct"], res
    assert res["checks"]["placement_mismatch"]["value"] == 0
    placed = res["metrics"]["pods_total"]["value"]
    assert placed >= 40 and placed % 8 == 0


def test_unsupported_gang_config_fails_at_load(tmp_path):
    import json
    import shutil

    shutil.copytree(DUMMY, tmp_path / "d")
    path = tmp_path / "d" / "configs" / "tinygang.json"
    cfg = json.loads(path.read_text())
    cfg["gangs"]["mode"] = "NonStrict"
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="only mode 'Strict'"):
        load_cell(str(tmp_path / "d"), "tinygang.trickle", bench_dir=DUMMY)
