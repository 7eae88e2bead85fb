"""A new configuration, traffic mix and metric are picked up from their
files and BENCHMARK.json entries alone: the dummy ones under
``tests/dummy`` run through the unchanged harness."""

import os

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
