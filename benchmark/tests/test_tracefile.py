"""The trace reduction, checked by hand on a small trace."""

import pytest

from benchmark.harness import tracefile as tf

# device operations (start_ns, end_ns, name) and host spans, window 0-60
OPS = [(0, 10, "fusion.1"), (5, 20, "dense_kernel"), (30, 40, "dense_kernel"),
       (50, 55, "copy.2"), (58, 70, "fusion.1")]
SPANS = [(15, 35, "bench.sync"), (38, 62, "bench.assign"),
         (44, 46, "bench.binder")]


def test_busy_is_the_union_clipped_to_the_window():
    # (0, 20) + (30, 40) + (50, 55) + (58, 60) = 20 + 10 + 5 + 2
    assert tf.merged(OPS, 0, 60) == [(0, 20), (30, 40), (50, 55), (58, 60)]
    assert tf.busy_ns(OPS, 0, 60) == 37
    assert tf.idle_share(OPS, 0, 60) == pytest.approx(1 - 37 / 60)


def test_kernel_time_sums_its_own_events():
    assert tf.kernel_ns(OPS, "dense_kernel", 0, 60) == (25, 2)
    assert tf.kernel_ns(OPS, "dense_kernel", 0, 35) == (20, 2)
    assert tf.kernel_ns(OPS, "nothing", 0, 60) == (0, 0)


def test_top_ops_and_idle_gaps():
    assert tf.top_ops(OPS, 0, 60) == [
        ["dense_kernel", 25e-9], ["fusion.1", 12e-9], ["copy.2", 5e-9]]
    # gaps (20, 30) mid 25 in sync; (40, 50) mid 45 in binder, the
    # innermost; (55, 58) mid 56.5 in assign
    assert tf.idle_gaps(OPS, SPANS, 0, 60) == [
        ["bench.sync", 10e-9], ["bench.binder", 10e-9],
        ["bench.assign", 3e-9]]


def test_ops_are_named_by_the_program_that_ran_them():
    ops = [(1, 2, "%fusion = s32[8] fusion(...)"), (12, 13, "%fusion = f"),
           (30, 31, "%copy = x")]
    programs = [(0, 10, "jit_a(123)"), (10, 20, "jit_b(456)")]
    assert [n for _, _, n in tf.named_by_module(ops, programs)] == [
        "jit_a/%fusion", "jit_b/%fusion", "?/%copy"]


def test_load_reads_the_host_spans_of_a_recorded_trace(tmp_path):
    import time

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.sync"):
            time.sleep(0.01)
        jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    devices, modules, host = tf.load(tf.find_xplane(str(tmp_path)))
    names = {name: (e - s) for s, e, name in host}
    assert set(names) == {"bench.window", "bench.sync"}
    assert names["bench.window"] >= names["bench.sync"] >= 1e7
    assert devices == modules == {}  # the CPU has no TPU device plane
