"""Compile truth (ISSUE 19): the XLA launch ledger.

Unit surfaces: boundary registration completeness across the serving
path, the attribution-off bit-inert contract (zero retraces, no ledger
mutation, identical results), first-compile AOT capture with
cost/memory attribution, attributed retrace events naming boundary +
shape signature, the /healthz ``device`` block end-to-end over HTTP,
the report CLI's golden shape and the admin profile capture.
"""

import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from koordinator_tpu.analysis.retrace_guard import retrace_guard  # noqa: E402
from koordinator_tpu.obs import devprof  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_ledger():
    devprof.reset()
    yield
    devprof.reset()


def _make_boundary(name="test.bound"):
    @devprof.boundary(name)
    @jax.jit
    def double(x):
        return x * 2

    return double


# the serving path's full boundary set: every jitted def under
# solver/ + parallel/ that the unregistered-jit-boundary lint guards
_EXPECTED = {
    "solver.greedy.score_cycle",
    "solver.greedy.greedy_assign",
    "solver.resident._scatter_flat",
    "solver.resident._scatter_flat_sharded",
    "solver.incremental._rescore",
    "solver.incremental._rescore_sharded",
    "solver.candidates._build",
    "solver.candidates._build_sharded",
    "solver.candidates._count_blocks",
    "solver.candidates._count_blocks_sharded",
    "solver.candidates._extract_block",
    "solver.candidates._refresh",
    "solver.candidates._refresh_sharded",
    "solver.candidates._score",
    "solver.candidates._score_sharded",
    "solver.candidates.sparse_top_k",
    "solver.topk.masked_top_k",
    "solver.terms._term_extras_jit",
    "solver.pallas_dense._run_cycle_dense",
    "solver.pallas_dense._greedy_assign_dense",
    "parallel.shard_assign._assign_sharded",
    "parallel.shard_assign._assign_waves",
}


class TestRegistrationCompleteness:
    def test_every_serving_boundary_is_wrapped(self):
        # reset() clears the registry, so check the durable marker the
        # decorator leaves on the wrapped callable instead of relying
        # on import-time registration order
        import importlib
        import inspect

        found = set()
        for mod_name in (
            "koordinator_tpu.solver.greedy",
            "koordinator_tpu.solver.resident",
            "koordinator_tpu.solver.incremental",
            "koordinator_tpu.solver.candidates",
            "koordinator_tpu.solver.topk",
            "koordinator_tpu.solver.terms",
            "koordinator_tpu.solver.pallas_dense",
            "koordinator_tpu.parallel.shard_assign",
        ):
            mod = importlib.import_module(mod_name)
            for _n, obj in inspect.getmembers(mod):
                tag = getattr(obj, "devprof_boundary", None)
                if isinstance(tag, str):
                    found.add(tag)
        assert found == _EXPECTED

    def test_decorator_registers_eagerly(self):
        _make_boundary("test.reg")
        assert "test.reg" in devprof.boundaries()


class TestBitInertOff:
    def test_sample_zero_is_the_default_and_off(self):
        assert not devprof.enabled()
        assert devprof.summary()["attribute"] is False

    def test_off_path_zero_retraces_and_no_ledger_state(self):
        fn = _make_boundary("test.off")
        x = jnp.arange(8, dtype=jnp.float32)
        np.asarray(fn(x))  # warm the one shape
        with retrace_guard(budget=0):
            out = np.asarray(fn(x))
        assert np.array_equal(out, np.arange(8) * 2)
        summ = devprof.summary()
        assert summ["boundaries"]["test.off"]["launches"] == 0
        assert summ["entries"] == [] and summ["retraces"] == []
        assert summ["boundaries"]["test.off"]["compiles"] == 0

    def test_off_result_identical_to_unwrapped(self):
        fn = _make_boundary("test.parity")
        x = jnp.arange(16, dtype=jnp.float32)
        assert np.array_equal(
            np.asarray(fn(x)), np.asarray(fn.__wrapped__(x))
        )


class TestSampledCapture:
    def test_cold_launch_captures_compile_truth(self):
        devprof.configure(attribute=True)
        fn = _make_boundary("test.cold")
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))
        summ = devprof.summary()
        (entry,) = summ["entries"]
        assert entry["boundary"] == "test.cold"
        assert "float32[8]" in entry["sig"]
        assert entry["backend"] == "cpu"
        assert entry["compile_ms"] is not None
        assert np.isfinite(entry["compile_ms"]) and entry["compile_ms"] > 0
        # XLA cost/memory attribution (version-gated: None is legal,
        # a present value must be finite and non-negative)
        for key in ("flops", "bytes_accessed"):
            v = entry[key]
            assert v is None or (np.isfinite(v) and v >= 0)
        assert summ["boundaries"]["test.cold"]["compiles"] == 1
        assert summ["boundaries"]["test.cold"]["launches"] == 1


    def test_warm_launches_count_without_recapture(self):
        devprof.configure(attribute=True)
        fn = _make_boundary("test.warmcount")
        x = jnp.arange(8, dtype=jnp.float32)
        for _ in range(4):
            np.asarray(fn(x))
        st = devprof.summary()["boundaries"]["test.warmcount"]
        assert st == {"launches": 4, "compiles": 1}
        assert len(devprof.summary()["entries"]) == 1

    def test_capture_alone_attributes_no_compile(self):
        # prewarm capture records replay specs; compile attribution is
        # its own switch
        devprof.configure(capture=True)
        fn = _make_boundary("test.captureonly")
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))
        summ = devprof.summary()
        assert summ["entries"] == []
        assert summ["boundaries"]["test.captureonly"]["compiles"] == 0
        assert [r["boundary"] for r in devprof.replay_records()] == [
            "test.captureonly"
        ]


class TestRetraceAttribution:
    def test_new_shape_is_an_attributed_event(self):
        devprof.configure(attribute=True)
        fn = _make_boundary("test.retrace")
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))
        assert devprof.summary()["retraces"] == []  # first compile
        np.asarray(fn(jnp.arange(9, dtype=jnp.float32)))
        (ev,) = devprof.summary()["retraces"]
        assert ev["boundary"] == "test.retrace"
        assert "float32[9]" in ev["sig"]
        assert ev["backend"] == "cpu"
        assert ev["compile_ms"] is not None and ev["compile_ms"] > 0

    def test_warm_shape_never_retraces(self):
        devprof.configure(attribute=True)
        fn = _make_boundary("test.stable")
        x = jnp.arange(8, dtype=jnp.float32)
        for _ in range(4):
            np.asarray(fn(x))
        assert devprof.summary()["retraces"] == []


class TestHealthBlock:
    def test_shape_and_ranking(self):
        devprof.configure(attribute=True)
        fn = _make_boundary("test.health")
        x = jnp.arange(8, dtype=jnp.float32)
        np.asarray(fn(x))
        np.asarray(fn(x))
        blk = devprof.health_block()
        assert blk["platform"] == "cpu"
        assert blk["device_count"] >= 1
        assert blk["attribute"] is True
        assert blk["registered_boundaries"] >= 1
        assert blk["compiles"] == 1
        assert blk["compile_ms_total"] > 0
        assert blk["retraces"] == 0
        assert set(blk) == {
            "platform", "device_count", "attribute",
            "registered_boundaries", "compiles", "compile_ms_total",
            "retraces",
        }

    def test_healthz_serves_device_block(self, tmp_path):
        """The daemon end-to-end: /healthz carries the ``device`` block
        from the same ledger the solver boundaries feed."""
        import urllib.request

        from koordinator_tpu.scheduler.server import SchedulerServer

        devprof.configure(attribute=True)
        s = SchedulerServer(
            lease_path=str(tmp_path / "l.lease"),
            uds_path=str(tmp_path / "scorer.sock"),
            enable_grpc=False,
        ).start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{s.http_port}/healthz", timeout=5
            ) as r:
                doc = json.loads(r.read())
            dev = doc["device"]
            assert dev["platform"] == "cpu"
            assert dev["attribute"] is True
            for key in ("device_count", "registered_boundaries",
                        "compiles", "compile_ms_total", "retraces"):
                assert key in dev
            assert "top" not in dev
        finally:
            s.stop()


class TestDumpAndReportCli:
    def test_dump_writes_ledger_json(self, tmp_path):
        devprof.configure(attribute=True, state_dir=str(tmp_path))
        fn = _make_boundary("test.dump")
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))
        path = devprof.dump()
        assert path == str(tmp_path / devprof.LEDGER_FILENAME)
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["attribute"] is True
        assert doc["entries"][0]["boundary"] == "test.dump"

    def test_report_cli_golden(self, tmp_path, capsys):
        devprof.configure(attribute=True, state_dir=str(tmp_path))
        fn = _make_boundary("test.report")
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))
        np.asarray(fn(jnp.arange(8, dtype=jnp.float32)))  # warm
        np.asarray(fn(jnp.arange(9, dtype=jnp.float32)))  # retrace
        devprof.dump()
        assert devprof.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "devprof ledger — backend=cpu attribute=True" in out
        assert "compile ledger:" in out
        assert "test.report" in out
        assert "float32[8]" in out and "float32[9]" in out
        assert "device time" not in out
        assert "attributed retraces (1):" in out

    def test_report_cli_missing_ledger_exits_2(self, tmp_path, capsys):
        assert devprof.main([str(tmp_path)]) == 2
        assert "no ledger" in capsys.readouterr().err


class TestNestedTraceBypass:
    def test_boundary_under_a_live_trace_is_unmeasured(self):
        devprof.configure(attribute=True)
        inner = _make_boundary("test.inner")

        @jax.jit
        def outer(x):
            return inner(x) + 1

        np.asarray(outer(jnp.arange(8, dtype=jnp.float32)))
        summ = devprof.summary()
        # the nested callsite never touched the ledger: no launches,
        # no AOT capture for the inner boundary
        assert summ["boundaries"].get(
            "test.inner", {"launches": 0}
        )["launches"] == 0
        assert all(
            e["boundary"] != "test.inner" for e in summ["entries"]
        )


class TestProfileCapture:
    def test_capture_returns_live_directory(self, tmp_path):
        import time

        out_dir = devprof.capture_profile(str(tmp_path), window_ms=50)
        assert os.path.isdir(out_dir)
        assert out_dir.startswith(
            os.path.join(str(tmp_path), "devprof_trace")
        )
        # run something during the window so the trace has content,
        # then give the background stop thread time to close it
        np.asarray(jnp.arange(8) * 2)
        time.sleep(0.3)
