"""The program's stages in a trace, checked by hand on a small trace."""

import pytest

from benchmark.harness import stages as st
from benchmark.harness import tracefile as tf

# test_tracefile.py's trace (window 0-60), and the program's stages
OPS = [(0, 10, "fusion.1"), (5, 20, "dense_kernel"), (30, 40, "dense_kernel"),
       (50, 55, "copy.2"), (58, 70, "fusion.1")]
BENCH = [(15, 35, "bench.sync"), (38, 62, "bench.assign"),
         (44, 46, "bench.binder")]
KOORD = [(16, 27, "koord.sync"), (18, 22, "koord.sync_decode"),
         (39, 61, "koord.assign")]


def test_idle_is_cut_at_every_span_edge():
    # gap (20, 30): [20, 22) sync_decode, [22, 27) sync, [27, 30)
    # bench.sync; gap (40, 50): [40, 44) and [46, 50) assign, [44, 46)
    # binder; gap (55, 58): assign
    got = st.idle_spans(OPS, BENCH + KOORD, 0, 60)
    assert [k for k, _ in got] == [
        "koord.assign", "koord.sync", "bench.sync", "bench.binder",
        "koord.sync_decode"]
    assert [v for _, v in got] == pytest.approx(
        [11e-9, 5e-9, 3e-9, 2e-9, 2e-9])


def test_idle_outside_every_span_is_none():
    # the window opens 5 ns before the first operation
    got = dict(st.idle_spans(OPS, BENCH, -5, 60))
    assert got == pytest.approx({"bench.assign": 11e-9, "bench.sync": 10e-9,
                                 "none": 5e-9, "bench.binder": 2e-9})


def test_idle_spans_charges_all_the_idle():
    total = sum(v for _, v in st.idle_spans(OPS, BENCH + KOORD, -5, 60))
    assert total == pytest.approx((65 - tf.busy_ns(OPS, -5, 60)) * 1e-9)


def test_rpc_idle_named_by_the_program():
    # idle inside bench.sync or bench.assign: all 23 ns of it; the
    # program's stages name all but bench.sync's [27, 30) and the
    # binder's [44, 46), which lies inside bench.assign
    inside, named = st.rpc_idle_named(OPS, BENCH + KOORD, 0, 60)
    assert inside == pytest.approx(23e-9)
    assert named == pytest.approx(18e-9)


def test_stage_means_per_cycle():
    spans = [(0, 10e6, "koord.client.sync"),
             (0, 2e6, "koord.client.sync_encode"),
             (3e6, 9e6, "koord.sync"), (4e6, 5e6, "koord.sync_apply"),
             (20e6, 30e6, "koord.client.sync"),
             (20e6, 22e6, "koord.client.sync_encode"),
             (23e6, 29e6, "koord.sync"), (24e6, 25e6, "koord.sync_apply"),
             (95e6, 99e6, "koord.sync")]  # starts outside the window
    stats = st.span_stats(spans, 0, 90e6)
    assert stats["koord.sync"] == [2, pytest.approx(0.012)]
    ms = st.stage_ms(stats, cycles=2)
    assert ms == {"sync_encode_ms": pytest.approx(2.0),
                  "sync_apply_ms": pytest.approx(1.0),
                  "sync_wire_ms": pytest.approx(2.0)}
    assert st.coverage(stats) == {"koord.sync": pytest.approx(1 / 6)}


def test_a_trace_without_stages_reads_nothing():
    stats = st.span_stats(BENCH, 0, 60)
    assert st.stage_ms(stats, cycles=1) == {}
    assert st.coverage(stats) == {}


def test_stock_readers_ignore_the_programs_spans(tmp_path):
    """A trace holding koord.* annotations reads, through tracefile,
    exactly what it reads without them."""
    import time

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sync"):
            with jax.profiler.TraceAnnotation("koord.client.sync"):
                time.sleep(0.01)
        jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = tf.find_xplane(str(tmp_path))
    _devices, _modules, host = tf.load(path)
    assert {name for _, _, name in host} == {"bench.window", "bench.sync"}
    both = st.host_spans(path)
    assert {name for _, _, name in both} == {
        "bench.window", "bench.sync", "koord.client.sync"}
    # the stock idle attribution reads the same with the stages present
    (lo, hi), spans = [(s, e) for s, e, n in host if n == "bench.window"][0], \
        [x for x in host if x[2] != "bench.window"]
    assert tf.idle_gaps([], spans, lo, hi) == tf.idle_gaps(
        [], [x for x in both if x[2].startswith("bench.")
             and x[2] != "bench.window"], lo, hi)


def test_a_traced_cell_reads_every_stage(tiny_cell):
    """The tool's run of a whole cell on the CPU, traced: every stage
    mean, the children's cover and the Sync bytes read."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "stages.py")
    spec = importlib.util.spec_from_file_location("stages_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    result, stages = tool.measure(tiny_cell("colo2k.batch"), 7, 1.0, True,
                                  out=lambda *a, **k: None)
    assert result["correct"]
    assert set(stages["stage_ms"]) == set(st.STAGE_SPANS) | {
        "sync_wire_ms"}
    assert set(stages["coverage"]) == {"koord.sync", "koord.assign"}
    assert stages["trace_cycles"] >= 1 and stages["cycles"] >= 1
    assert stages["sync_wire_kib"] > 0
    assert stages["rpc_idle_named_s"] <= stages["rpc_idle_s"]
