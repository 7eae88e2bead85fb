"""ISSUE 4: cycle telemetry — spans, scorer /metrics, flight recorder.

Covers the subsystem contracts end to end:

* span recorder mechanics (cycle ids, nesting, bounded buffers, notes);
* metrics registry histogram rendering + IDEMPOTENT family
  registration (the duplicate # HELP/# TYPE fix);
* flight recorder ring wraparound, dump-on-error,
  dump-on-SIGUSR1, and schema validation of every written dump;
* a REAL cycle through the ScorerServicer populating the scorer
  families, served in valid Prometheus text from the daemon's /metrics;
* the raw-UDS transport counting (not silently dropping) malformed
  frames.
"""

import json
import os
import signal
import socket
import struct
import tempfile
import time

import numpy as np
import pytest

from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.koordlet.metrics import MetricsRegistry
from koordinator_tpu.obs import CycleTelemetry, validate_flight_dump
from koordinator_tpu.obs.flight import FlightRecorder
from koordinator_tpu.obs.spans import MAX_SPANS_PER_CYCLE, SpanRecorder

from test_resident_warm import _full_sync_request, _random_state


def _servicer(tmp=None, gang_free=False, mesh=None):
    kwargs = {"state_dir": tmp} if tmp else {}
    if mesh is not None:
        kwargs["mesh"] = mesh
    sv = ScorerServicer(**kwargs)
    rng = np.random.RandomState(3)
    state = _random_state(rng, n_nodes=4, n_pods=8, with_quota=False)
    if gang_free:
        state["pod_gang"][:] = -1
    reply = sv.sync(_full_sync_request(state))
    return sv, state, reply


class TestSpanRecorder:
    def test_cycle_ids_correlate_with_epoch(self):
        rec = SpanRecorder(epoch="abc123")
        assert rec.current().cycle_id == "cabc123-1"
        rec.commit()
        assert rec.current().cycle_id == "cabc123-2"

    def test_client_cycle_id_adopted_and_spans_recorded(self):
        rec = SpanRecorder(epoch="e")
        with rec.span("sync_decode"):
            pass
        cyc = rec.current(snapshot_id="se-1", cycle_id="client-7")
        with rec.span("dispatch"):
            with rec.span("inner"):
                pass
        record = rec.commit()
        assert record["cycle_id"] == "client-7"
        assert cyc.cycle_id == "client-7"
        assert record["snapshot_id"] == "se-1"
        assert [s["name"] for s in record["spans"]] == [
            "sync_decode", "dispatch", "inner",
        ]
        assert all(s["dur_ms"] is not None for s in record["spans"])

    def test_unended_span_is_visible_not_invented(self):
        rec = SpanRecorder()
        rec.begin_span("leaky")  # koordlint: disable=span-leak(the leak IS the fixture)
        record = rec.commit(error="boom")
        assert record["spans"][0]["dur_ms"] is None
        assert record["error"] == "boom"

    def test_span_buffer_is_bounded(self):
        rec = SpanRecorder()
        for i in range(MAX_SPANS_PER_CYCLE + 10):
            with rec.span(f"s{i}"):
                pass
        record = rec.commit()
        assert len(record["spans"]) == MAX_SPANS_PER_CYCLE
        assert record["span_overflow"] == 10

    def test_notes_carry_host_scalars(self):
        rec = SpanRecorder()
        rec.note("rounds", 17)
        rec.note("path", "wave")
        assert rec.commit()["notes"] == {"rounds": 17, "path": "wave"}


class TestMetricsRegistryFamilies:
    def test_histogram_renders_valid_prometheus_text(self):
        m = MetricsRegistry()
        m.register("h_ms", "histogram", "a histogram", buckets=(1.0, 10.0, float("inf")))
        m.histogram_observe("h_ms", 0.5, {"path": "scan"})
        m.histogram_observe("h_ms", 5.0, {"path": "scan"})
        m.histogram_observe("h_ms", 100.0, {"path": "scan"})
        text = m.render()
        assert text.count("# TYPE h_ms histogram") == 1
        assert 'h_ms_bucket{path="scan",le="1"} 1' in text
        assert 'h_ms_bucket{path="scan",le="10"} 2' in text
        assert 'h_ms_bucket{path="scan",le="+Inf"} 3' in text
        assert 'h_ms_sum{path="scan"} 105.5' in text
        assert 'h_ms_count{path="scan"} 3' in text
        assert m.get_histogram("h_ms", {"path": "scan"}) == (3, 105.5)

    def test_reregistration_is_idempotent_no_duplicate_type_lines(self):
        """The satellite fix: a daemon restart re-registering its
        families must not duplicate # HELP/# TYPE lines."""
        m = MetricsRegistry()
        for _ in range(3):  # three "restarts"
            m.register("koord_ticks_total", "counter", "ticks")
        m.counter_add("koord_ticks_total", 1)
        text = m.render()
        assert text.count("# TYPE koord_ticks_total counter") == 1
        assert text.count("# HELP koord_ticks_total") == 1

    def test_kind_conflict_raises_instead_of_duplicating(self):
        """The pre-fix hole: one name landing as BOTH counter and gauge
        rendered the family twice (invalid exposition).  Now the second
        kind is rejected loudly."""
        m = MetricsRegistry()
        m.counter_add("x_total", 1)
        with pytest.raises(ValueError, match="already registered"):
            m.gauge_set("x_total", 5)
        with pytest.raises(ValueError, match="already registered"):
            m.register("x_total", "gauge")
        assert m.render().count("# TYPE x_total") == 1

    def test_describe_then_write_binds_kind_once(self):
        m = MetricsRegistry()
        m.describe("g", "a gauge")
        m.gauge_set("g", 2.0)
        text = m.render()
        assert "# HELP g a gauge" in text
        assert text.count("# TYPE g gauge") == 1

    def test_custom_buckets_gain_inf_bound(self):
        # Prometheus requires le="+Inf" == _count; a custom bucket list
        # omitting it must be normalized, not silently drop over-top
        # observations from every bucket
        m = MetricsRegistry()
        m.register("y_ms", "histogram", buckets=(1.0, 10.0))
        m.histogram_observe("y_ms", 50.0)
        text = m.render()
        assert 'y_ms_bucket{le="+Inf"} 1' in text
        assert "y_ms_count 1" in text

    def test_describe_then_register_binds_not_conflicts(self):
        # the review-caught hole: describe() creates a kindless
        # placeholder; register() must bind it, not see a conflict
        m = MetricsRegistry()
        m.describe("x_total", "described first")
        m.register("x_total", "counter")
        m.counter_add("x_total", 1)
        assert m.render().count("# TYPE x_total counter") == 1

    def test_wsgi_app_serves_exposition(self):
        m = MetricsRegistry()
        m.counter_add("c_total", 2)
        captured = {}

        def sr(status, headers):
            captured["status"] = status
            captured["headers"] = dict(headers)

        body = b"".join(m.wsgi_app({}, sr))
        assert captured["status"].startswith("200")
        assert "text/plain" in captured["headers"]["Content-Type"]
        assert b"c_total 2" in body


class TestFlightRecorder:
    def _record(self, i):
        return {
            "cycle_id": f"c-{i}",
            "snapshot_id": f"s-{i}",
            "started_unix": 1000.0 + i,
            "spans": [{"name": "dispatch", "start_ms": 0.0, "dur_ms": 1.0}],
            "notes": {"path": "scan"},
            "error": None,
            "span_overflow": 0,
        }

    def test_ring_wraparound_keeps_last_k(self):
        fr = FlightRecorder(capacity=4)
        for i in range(11):
            fr.record(self._record(i))
        cycles = fr.snapshot()
        assert [c["cycle_id"] for c in cycles] == [
            "c-7", "c-8", "c-9", "c-10",
        ]
        assert fr.dropped == 7
        assert len(fr) == 4

    def test_dump_writes_schema_valid_json(self, tmp_path):
        fr = FlightRecorder(
            capacity=8, state_dir=str(tmp_path),
            config={"fit_scoring_strategy": "MostAllocated", "epoch": "e1"},
        )
        for i in range(3):
            fr.record(self._record(i))
        path = fr.dump("manual")
        assert path and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert validate_flight_dump(doc) == []
        assert doc["reason"] == "manual"
        assert doc["config"]["fit_scoring_strategy"] == "MostAllocated"
        assert [c["cycle_id"] for c in doc["cycles"]] == ["c-0", "c-1", "c-2"]

    def test_dump_without_state_dir_is_none(self):
        fr = FlightRecorder()
        fr.record(self._record(0))
        assert fr.dump("manual") is None

    def test_invalid_document_is_suppressed_not_written(self, tmp_path):
        fr = FlightRecorder(state_dir=str(tmp_path))
        fr.record({"cycle_id": ""})  # violates the schema
        assert fr.dump("manual") is None
        assert not os.path.exists(os.path.join(tmp_path, "flight")) or not os.listdir(
            os.path.join(tmp_path, "flight")
        )

    def test_schema_rejects_each_malformed_shape(self):
        good = {
            "version": 1, "reason": "r", "dumped_at_unix": 1.0,
            "config": {}, "dropped_cycles": 0,
            "cycles": [self._record(0)],
        }
        assert validate_flight_dump(good) == []
        assert validate_flight_dump([]) != []
        for key, bad in (
            ("version", 2),
            ("reason", ""),
            ("dumped_at_unix", float("nan")),
            ("config", None),
            ("dropped_cycles", -1),
            ("cycles", {}),
        ):
            doc = dict(good)
            doc[key] = bad
            assert validate_flight_dump(doc), key
        bad_cycle = dict(self._record(0))
        bad_cycle["spans"] = [{"name": "", "start_ms": -1, "dur_ms": "x"}]
        doc = dict(good)
        doc["cycles"] = [bad_cycle]
        problems = validate_flight_dump(doc)
        assert len(problems) >= 3

    def test_sigusr1_dumps_the_ring(self, tmp_path):
        fr = FlightRecorder(state_dir=str(tmp_path))
        fr.record(self._record(0))
        assert fr.install_sigusr1()  # pytest runs in the main thread
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.time() + 5.0
            flight_dir = os.path.join(tmp_path, "flight")
            while time.time() < deadline:
                if os.path.isdir(flight_dir) and any(
                    "sigusr1" in f for f in os.listdir(flight_dir)
                ):
                    break
                time.sleep(0.01)
            dumps = [f for f in os.listdir(flight_dir) if "sigusr1" in f]
            assert dumps, "SIGUSR1 produced no flight dump"
            with open(os.path.join(flight_dir, dumps[0])) as f:
                assert validate_flight_dump(json.load(f)) == []
        finally:
            signal.signal(signal.SIGUSR1, signal.SIG_DFL)

    def test_dump_pruning_bounds_the_directory(self, tmp_path):
        from koordinator_tpu.obs import flight as flight_mod

        fr = FlightRecorder(state_dir=str(tmp_path))
        fr.min_dump_interval_s = 0.0
        fr.record(self._record(0))
        for _ in range(flight_mod.MAX_DUMPS_KEPT + 5):
            assert fr.dump("loop")
        flight_dir = os.path.join(tmp_path, "flight")
        assert len(os.listdir(flight_dir)) == flight_mod.MAX_DUMPS_KEPT

    def test_dump_rate_limit_suppresses_floods(self, tmp_path):
        """A trigger storm (error loop, misbehaving client) must not
        stall serving on per-event disk I/O or churn real dumps out of
        the pruned directory; sigusr1 is exempt (the operator asked)."""
        fr = FlightRecorder(state_dir=str(tmp_path))
        fr.record(self._record(0))
        assert fr.dump("cycle-error")
        assert fr.dump("cycle-error") is None  # inside the interval
        assert fr.dumps_suppressed == 1
        assert fr.dump("bad-frame")  # distinct reason: own limiter
        assert fr.dump("sigusr1") and fr.dump("sigusr1")  # never limited

    def test_failed_write_does_not_close_the_rate_window(self, tmp_path,
                                                         monkeypatch):
        """The limiter stamps AFTER a successful write: a transient
        write failure (ENOSPC) must not suppress the retry that would
        have produced the post-mortem file."""
        fr = FlightRecorder(state_dir=str(tmp_path))
        fr.record(self._record(0))

        def boom(src, dst):
            raise OSError("disk full")

        monkeypatch.setattr(os, "replace", boom)
        assert fr.dump("cycle-error") is None
        monkeypatch.undo()
        assert fr.dump("cycle-error")  # immediately retryable
        assert fr.dump("cycle-error") is None  # NOW the window is closed


class TestServicerTelemetry:
    def test_real_cycle_populates_scorer_families(self, tmp_path):
        sv, state, reply = _servicer(str(tmp_path))
        rep = sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        reg = sv.telemetry.registry
        # acceptance: cycle latency, rounds/cycles, and cache-miss
        # counters populated after a real cycle
        count, total = reg.get_histogram(
            "koord_scorer_cycle_latency_ms", {"path": rep.path, "wave": "1"}
        )
        assert count == 1 and total > 0
        assert reg.get("koord_scorer_cycles_total", {"path": rep.path}) == 1
        assert reg.get("koord_scorer_sync_total", {"kind": "full"}) == 1
        assert reg.get("koord_scorer_snapshot_generation") == 1
        assert (
            reg.get("koord_scorer_jit_cache_miss_total", {"kind": "trace"})
            or 0
        ) > 0, "the first cycle's compiles must show as cache misses"
        text = reg.render()
        assert text.count("# TYPE koord_scorer_cycle_latency_ms histogram") == 1

    def test_scalar_only_sync_counts_as_scalar_not_delta(self, tmp_path):
        sv, state, reply = _servicer(str(tmp_path))
        req = pb2.SyncRequest()
        req.nodes.metric_fresh.extend([True] * len(state["node_fresh"]))
        sv.sync(req)
        reg = sv.telemetry.registry
        assert reg.get("koord_scorer_sync_total", {"kind": "scalar"}) == 1
        assert not reg.get("koord_scorer_sync_total", {"kind": "delta"})

    def test_cycle_id_echoed_and_minted(self, tmp_path):
        sv, state, reply = _servicer(str(tmp_path))
        rep = sv.assign(
            pb2.AssignRequest(
                snapshot_id=reply.snapshot_id, cycle_id="plugin-42"
            )
        )
        assert rep.cycle_id == "plugin-42"
        rec = sv.telemetry.flight.snapshot()[-1]
        assert rec["cycle_id"] == "plugin-42"
        rep2 = sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        assert rep2.cycle_id.startswith(f"c{sv._epoch}-")

    def test_cycle_records_carry_pipeline_spans(self, tmp_path):
        sv, state, reply = _servicer(str(tmp_path))
        sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        rec = sv.telemetry.flight.snapshot()[-1]
        names = [s["name"] for s in rec["spans"]]
        assert "sync_decode" in names  # the Sync stage of this cycle
        assert "dispatch" in names and "readback" in names
        assert rec["notes"]["path"] in ("scan", "pallas")
        assert rec["snapshot_id"] == reply.snapshot_id

    def test_wave_cycle_notes_rounds(self, tmp_path):
        """The mesh servicer's round-based cycle notes its rounds, and
        its latency series carries the wave width that ran."""
        import jax

        from koordinator_tpu.parallel import cluster_mesh
        from koordinator_tpu.parallel.shard_assign import WAVE_WIDTH

        if len(jax.devices()) < 4:
            pytest.skip("needs 4 (virtual) devices")
        # a snapshot with Strict gangs is served off the shard path
        sv, state, reply = _servicer(
            str(tmp_path), gang_free=True,
            mesh=cluster_mesh(jax.devices()[:4]),
        )
        sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        rec = sv.telemetry.flight.snapshot()[-1]
        assert rec["notes"]["path"] == "shard"
        assert rec["notes"]["rounds"] >= 1
        reg = sv.telemetry.registry
        assert reg.get("koord_scorer_cycle_rounds", {"path": "shard"}) >= 1
        count, _ = reg.get_histogram(
            "koord_scorer_cycle_latency_ms",
            {"path": "shard", "wave": str(WAVE_WIDTH)},
        )
        assert count == 1

    def test_sync_score_assign_correlates_one_record(self, tmp_path):
        """The standard plugin flow (Sync → Score → Assign(cycle_id)):
        the flight record pulled by the client's cycle id must contain
        the sync AND score AND assign stages — Score must not commit
        the pending cycle out from under the correlation."""
        sv, state, reply = _servicer(str(tmp_path))
        sv.score(pb2.ScoreRequest(
            snapshot_id=reply.snapshot_id, top_k=4, flat=True
        ))
        assert len(sv.telemetry.flight) == 0  # nothing committed yet
        sv.assign(pb2.AssignRequest(
            snapshot_id=reply.snapshot_id, cycle_id="plugin-xyz"
        ))
        records = sv.telemetry.flight.snapshot()
        assert [r["cycle_id"] for r in records] == ["plugin-xyz"]
        names = [s["name"] for s in records[0]["spans"]]
        assert "sync_decode" in names
        assert "score_dispatch" in names and "score_readback" in names
        assert "dispatch" in names and "readback" in names
        # a Score with NO pending cycle commits its own record — first
        # a LAUNCHED one (memo invalidated so the batch really runs)...
        sv._score_memo.invalidate()
        sv.score(pb2.ScoreRequest(
            snapshot_id=reply.snapshot_id, top_k=4, flat=True
        ))
        records = sv.telemetry.flight.snapshot()
        assert len(records) == 2
        assert records[-1]["notes"]["path"] == "score"
        # ... then a memo-served one (ISSUE 7): still its own record,
        # labeled path="memo" with the memo_hit note, so prefix slices
        # never masquerade as device cycles
        sv.score(pb2.ScoreRequest(
            snapshot_id=reply.snapshot_id, top_k=4, flat=True
        ))
        records = sv.telemetry.flight.snapshot()
        assert len(records) == 3
        assert records[-1]["notes"]["path"] == "memo"
        assert records[-1]["notes"]["memo_hit"] is True
        # the memo record still says which snapshot it certified
        assert records[-1]["snapshot_id"] == reply.snapshot_id

    def test_concurrent_assigns_get_exact_records(self, tmp_path):
        """ISSUE 6 correlation fix #1: each Assign RPC records on its
        OWN span scope — a sibling can no longer relabel the open cycle
        or land stray stamps on it.  One record per RPC, each under its
        own cycle id, exactly one carrying the device-cycle spans."""
        import threading

        sv, state, reply = _servicer(str(tmp_path))
        n = 4
        ids = [f"rpc-{i}" for i in range(n)]
        barrier = threading.Barrier(n)

        def worker(i):
            barrier.wait()
            sv.assign(pb2.AssignRequest(
                snapshot_id=reply.snapshot_id, cycle_id=ids[i]
            ))

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        records = sv.telemetry.flight.snapshot()
        assert sorted(r["cycle_id"] for r in records) == sorted(ids)
        with_dispatch = [
            r for r in records
            if any(s["name"] == "dispatch" for s in r["spans"])
        ]
        assert len(with_dispatch) == 1, (
            "exactly one RPC owns the device cycle; the rest are memo "
            "records that must not carry its spans"
        )
        # the owner's record adopted the pending sync correlation
        names = [s["name"] for s in with_dispatch[0]["spans"]]
        assert "sync_decode" in names and "readback" in names
        for r in records:
            if r is with_dispatch[0]:
                continue
            assert r["notes"].get("memo_hit") is True
            assert r["notes"]["path"] == "memo"
            assert not any(
                s["name"] in ("dispatch", "sync_decode") for s in r["spans"]
            )

    def test_displaced_assign_records_its_own_cycle(self, tmp_path):
        """ISSUE 6 correlation fix #2: an Assign displaced mid-queue by
        another client's Sync used to leave its stamps on the pending
        cycle (another client's correlation).  Now its own record says
        'displaced' — ring-visible, no disk dump, no error counter —
        and the new pending cycle stays pristine."""
        from koordinator_tpu.bridge.state import numpy_to_tensor

        sv, state, reply = _servicer(str(tmp_path))
        old_sid = reply.snapshot_id

        prev = state["node_usage"].copy()
        state["node_usage"][0, 1] += 5
        delta = pb2.SyncRequest()
        delta.nodes.usage.CopyFrom(
            numpy_to_tensor(state["node_usage"], prev)
        )
        orig = sv.dispatch.run_pipelined

        def hijack(launch_fn):
            # a Sync lands between the RPC-entry generation check and
            # the launch: exactly the displacement interleaving
            sv.dispatch.run_pipelined = orig
            sv.sync(delta)
            return orig(launch_fn)

        sv.dispatch.run_pipelined = hijack
        with pytest.raises(ValueError, match="not resident"):
            sv.assign(pb2.AssignRequest(
                snapshot_id=old_sid, cycle_id="victim"
            ))
        records = sv.telemetry.flight.snapshot()
        assert [r["cycle_id"] for r in records] == ["victim"]
        assert "not resident" in records[0]["error"]
        assert records[0]["notes"].get("displaced") is True
        # client-protocol condition: visible in the ring, but neither a
        # flight dump nor a cycle error
        flight_dir = os.path.join(tmp_path, "flight")
        assert not os.path.isdir(flight_dir) or not os.listdir(flight_dir)
        assert not sv.telemetry.registry.get(
            "koord_scorer_cycle_errors_total", {"stage": "assign"}
        )
        # the delta Sync's pending correlation survived untouched and
        # reaches the NEXT assign's record intact
        assert sv.telemetry.spans.has_pending()
        sv.assign(pb2.AssignRequest(
            snapshot_id=sv.snapshot_id(), cycle_id="survivor"
        ))
        rec = sv.telemetry.flight.snapshot()[-1]
        assert rec["cycle_id"] == "survivor"
        assert "sync_decode" in [s["name"] for s in rec["spans"]]

    def test_rejected_sync_frame_counts_only(self, tmp_path):
        """A client-rejectable frame (validation ValueError) bumps the
        error counter and NOTHING else: no ring record (a looping bad
        client must not churn the 64-slot ring), no disk dump, and the
        pending cycle — possibly holding another client's sync spans
        awaiting THEIR Assign — stays open and correlatable."""
        sv, state, reply = _servicer(str(tmp_path))
        assert sv.telemetry.spans.has_pending()  # good sync's spans
        bad = pb2.SyncRequest()
        bad.nodes.usage.shape.extend(state["node_usage"].shape)
        bad.nodes.usage.delta_idx = np.asarray([5, 5], "<i8").tobytes()
        bad.nodes.usage.delta_val = np.asarray([1, 2], "<i8").tobytes()
        with pytest.raises(ValueError, match="duplicate"):
            sv.sync(bad)
        assert sv.telemetry.registry.get(
            "koord_scorer_cycle_errors_total", {"stage": "sync"}
        ) == 1
        assert len(sv.telemetry.flight) == 0
        assert sv.telemetry.spans.has_pending()
        flight_dir = os.path.join(tmp_path, "flight")
        assert not os.path.isdir(flight_dir) or not os.listdir(flight_dir)
        # the good sync's spans still reach the eventual Assign record
        sv.assign(pb2.AssignRequest(
            snapshot_id=reply.snapshot_id, cycle_id="after-bad-frame"
        ))
        rec = sv.telemetry.flight.snapshot()[-1]
        assert rec["cycle_id"] == "after-bad-frame"
        assert "sync_decode" in [s["name"] for s in rec["spans"]]

    def test_sync_score_only_stream_commits_backlog_records(self, tmp_path):
        """A replica that never Assigns (e.g. a non-leader: Score/Sync
        serve, Assign refused) must still populate the flight ring —
        past the span threshold the pending cycle commits as a backlog
        record instead of growing one immortal cycle."""
        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.obs import CycleTelemetry

        sv, state, reply = _servicer(str(tmp_path))
        for i in range(CycleTelemetry.PENDING_COMMIT_SPANS + 4):
            prev = state["node_usage"].copy()
            state["node_usage"][0, 1] += 1
            req = pb2.SyncRequest()
            req.nodes.usage.CopyFrom(
                numpy_to_tensor(state["node_usage"], prev)
            )
            sv.sync(req)
        records = sv.telemetry.flight.snapshot()
        assert records, "sync-only stream never committed a record"
        assert records[0]["notes"].get("backlog") is True
        assert records[0]["error"] is None
        # the pending cycle is bounded, not immortal
        assert (
            len(sv.telemetry.spans.current().spans)
            < CycleTelemetry.PENDING_COMMIT_SPANS + 8
        )

    def test_cycle_error_dumps_flight(self, tmp_path, monkeypatch):
        sv, state, reply = _servicer(str(tmp_path))
        import koordinator_tpu.bridge.server as server_mod

        def boom(*a, **kw):
            raise RuntimeError("device on fire")

        monkeypatch.setattr(server_mod, "run_cycle", boom)
        with pytest.raises(RuntimeError, match="device on fire"):
            sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        flight_dir = os.path.join(tmp_path, "flight")
        dumps = [f for f in os.listdir(flight_dir) if "cycle-error" in f]
        assert dumps, "a failed cycle must dump the flight ring"
        with open(os.path.join(flight_dir, dumps[0])) as f:
            doc = json.load(f)
        assert validate_flight_dump(doc) == []
        last = doc["cycles"][-1]
        assert "device on fire" in last["error"]
        reg = sv.telemetry.registry
        assert reg.get(
            "koord_scorer_cycle_errors_total", {"stage": "assign"}
        ) == 1

    def test_kernel_fault_fails_assign_and_dumps(self, tmp_path,
                                                 monkeypatch):
        """A fault inside the Pallas kernel itself (the TPU branch of
        run_cycle, routed on the CPU platform) fails the Assign and
        dumps the flight ring as a cycle error — never a silent
        demotion to the scan."""
        import jax

        from koordinator_tpu.solver import pallas_dense

        sv, state, reply = _servicer(str(tmp_path))

        def boom(*a, **kw):
            raise RuntimeError("mosaic refused the kernel")

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pallas_dense, "greedy_assign_dense", boom)
        with pytest.raises(RuntimeError, match="mosaic refused"):
            sv.assign(pb2.AssignRequest(snapshot_id=reply.snapshot_id))
        flight_dir = os.path.join(tmp_path, "flight")
        dumps = [f for f in os.listdir(flight_dir) if "cycle-error" in f]
        assert dumps
        with open(os.path.join(flight_dir, dumps[0])) as f:
            doc = json.load(f)
        assert validate_flight_dump(doc) == []
        assert "mosaic refused" in doc["cycles"][-1]["error"]
        assert sv.telemetry.registry.get(
            "koord_scorer_cycle_errors_total", {"stage": "assign"}
        ) == 1


class TestDaemonMetricsEndpoint:
    def test_metrics_endpoint_serves_scorer_families(self, tmp_path):
        """Acceptance: /metrics on the bridge daemon serves the scorer
        families in valid Prometheus text after a real cycle."""
        import urllib.request

        from koordinator_tpu.scheduler.server import SchedulerServer

        s = SchedulerServer(
            lease_path=str(tmp_path / "leader.lease"),
            uds_path=str(tmp_path / "scorer.sock"),
            http_port=0,
            enable_grpc=False,
            state_dir=str(tmp_path / "state"),
        ).start()
        try:
            deadline = time.time() + 10
            while not s.elector.is_leader and time.time() < deadline:
                time.sleep(0.05)
            rng = np.random.RandomState(5)
            state = _random_state(rng, n_nodes=4, n_pods=8, with_quota=False)
            reply = s.servicer.sync(_full_sync_request(state))
            s.servicer.assign(
                pb2.AssignRequest(snapshot_id=reply.snapshot_id)
            )
            # a fresh jit program guarantees at least one cache miss
            # lands while this daemon's telemetry is live (the cycle's
            # own programs may already be warm from earlier tests)
            import jax
            import jax.numpy as jnp

            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))
            with urllib.request.urlopen(
                f"http://127.0.0.1:{s.http_port}/metrics", timeout=5
            ) as resp:
                text = resp.read().decode()
        finally:
            s.stop()
        # valid exposition: every family exactly one TYPE line
        for family in (
            "koord_scorer_cycle_latency_ms",
            "koord_scorer_cycles_total",
            "koord_scorer_sync_total",
            "koord_scheduler_leader",
        ):
            assert text.count(f"# TYPE {family} ") == 1, family
        assert "koord_scorer_cycle_latency_ms_count" in text
        assert "koord_scorer_jit_cache_miss_total" in text
        # histogram series parse as "name{labels} value" lines
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            name_part, _, value = line.rpartition(" ")
            assert name_part and float(value) is not None


class TestUdsMalformedFrames:
    def _connect(self, tmp):
        from koordinator_tpu.bridge.udsserver import RawUdsServer

        path = os.path.join(tmp, "scorer.sock")
        srv = RawUdsServer(path).start()
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(path)
        return srv, conn

    def _reg(self, srv):
        return srv.servicer.telemetry.registry

    def test_oversized_frame_counted_and_refused(self, tmp_path):
        srv, conn = self._connect(str(tmp_path))
        try:
            conn.sendall(struct.pack(">BI", 1, 1 << 30))
            status, length = struct.unpack(">BI", conn.recv(5, socket.MSG_WAITALL))
            body = conn.recv(length)
            assert status == 1 and b"too large" in body
            deadline = time.time() + 5
            while time.time() < deadline:
                if self._reg(srv).get(
                    "koord_scorer_uds_malformed_total", {"reason": "oversized"}
                ):
                    break
                time.sleep(0.01)
            assert self._reg(srv).get(
                "koord_scorer_uds_malformed_total", {"reason": "oversized"}
            ) == 1
        finally:
            conn.close()
            srv.stop()

    def test_unknown_method_counted_connection_survives(self, tmp_path):
        srv, conn = self._connect(str(tmp_path))
        try:
            conn.sendall(struct.pack(">BI", 77, 0))
            status, length = struct.unpack(">BI", conn.recv(5, socket.MSG_WAITALL))
            conn.recv(length)
            assert status == 1
            # the connection still serves real requests afterwards
            rng = np.random.RandomState(2)
            state = _random_state(rng, 4, 8, False)
            payload = _full_sync_request(state).SerializeToString()
            conn.sendall(struct.pack(">BI", 1, len(payload)) + payload)
            status, length = struct.unpack(">BI", conn.recv(5, socket.MSG_WAITALL))
            assert status == 0
            conn.recv(length)
            assert self._reg(srv).get(
                "koord_scorer_uds_malformed_total",
                {"reason": "unknown-method"},
            ) == 1
            assert self._reg(srv).get(
                "koord_scorer_uds_frames_total", {"method": "sync"}
            ) == 1
        finally:
            conn.close()
            srv.stop()

    def test_truncated_frame_counted_on_disconnect(self, tmp_path):
        srv, conn = self._connect(str(tmp_path))
        try:
            # a header promising 100 bytes, then hang up mid-payload
            conn.sendall(struct.pack(">BI", 1, 100) + b"only-ten--")
            conn.close()
            deadline = time.time() + 5
            while time.time() < deadline:
                if self._reg(srv).get(
                    "koord_scorer_uds_malformed_total",
                    {"reason": "truncated-payload"},
                ):
                    break
                time.sleep(0.01)
            assert self._reg(srv).get(
                "koord_scorer_uds_malformed_total",
                {"reason": "truncated-payload"},
            ) == 1
        finally:
            srv.stop()

    def test_clean_disconnect_is_not_malformed(self, tmp_path):
        srv, conn = self._connect(str(tmp_path))
        try:
            conn.close()
            time.sleep(0.2)
            reg = self._reg(srv)
            for reason in ("truncated-header", "truncated-payload"):
                assert not reg.get(
                    "koord_scorer_uds_malformed_total", {"reason": reason}
                )
        finally:
            srv.stop()
