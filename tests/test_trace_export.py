"""Distributed tracing (ISSUE 14): span identity/links, the export
sink's bounds, offline assembly, and the cross-process end-to-end —
one request, one tree, across client + server + coalesced launch.

The chaos-side acceptance (across a leader kill, brownout window,
retries) lives in tests/test_chaos_trace.py; this file owns the unit
surfaces and the happy-path integration.
"""

import json
import os

import pytest

from koordinator_tpu.obs import assemble as assemble_mod
from koordinator_tpu.obs.export import SpanExporter, resolve_export_dir
from koordinator_tpu.obs.spans import (
    ClientTraceOp,
    LINK_FANIN,
    SpanRecorder,
    TraceSpan,
    mint_span_id,
    mint_trace_id,
)


class TestTraceSpan:
    def test_record_shape_and_links(self):
        sink = []
        span = TraceSpan(
            "score", "t" * 32, "abcd", parent_id="p1", kind="server",
            sink=sink.append, attrs={"band": "koord-prod"},
        )
        span.link("u" * 32, "launch1")
        span.set_attr("top_k", 8)
        span.end()
        assert len(sink) == 1
        rec = sink[0]
        assert rec["traceId"] == "t" * 32
        assert rec["spanId"] == "abcd"
        assert rec["parentSpanId"] == "p1"
        assert rec["kind"] == "server"
        assert rec["status"] == {"code": "OK"}
        assert rec["attributes"] == {"band": "koord-prod", "top_k": 8}
        assert rec["links"] == [
            {"traceId": "u" * 32, "spanId": "launch1",
             "type": LINK_FANIN}
        ]
        assert rec["endTimeUnixNano"] >= rec["startTimeUnixNano"]

    def test_end_is_idempotent_and_abort_wins_first(self):
        sink = []
        span = TraceSpan("x", "t1", "s1", sink=sink.append)
        span.abort(RuntimeError("boom"))
        span.end()  # the finally-after-abort shape must not re-export
        assert len(sink) == 1
        assert sink[0]["status"]["code"] == "ERROR"
        assert "boom" in sink[0]["status"]["message"]

    def test_context_manager_aborts_on_exception(self):
        sink = []
        with pytest.raises(ValueError):
            with TraceSpan("x", "t1", "s2", sink=sink.append):
                raise ValueError("inner")
        assert sink[0]["status"]["code"] == "ERROR"

    def test_link_ref_none_is_noop(self):
        span = TraceSpan("x", "t1", "s3")
        span.link_ref(None)
        assert span.links == []

    def test_recorder_span_ids_deterministic_under_pinned_epoch(self):
        rec = SpanRecorder(epoch="feedf00d")
        assert rec.mint_span_id() == "spfeedf00d-1"
        assert rec.mint_span_id() == "spfeedf00d-2"
        # empty trace id = tracing off for this request: no span
        assert rec.start_trace_span("score", "") is None
        span = rec.start_trace_span("score", "t" * 32)
        assert span is not None and span.span_id == "spfeedf00d-3"
        span.end()

    def test_client_op_one_trace_per_logical_request(self):
        sink = []
        op = ClientTraceOp("score", sink=sink.append)
        a1 = op.attempt("replica-0")
        a1.abort(RuntimeError("shed"))
        a2 = op.attempt("replica-1")
        a2.set_attr("server_span", "sp1")
        a2.end()
        op.finish()
        assert len(sink) == 3
        trace_ids = {r["traceId"] for r in sink}
        assert trace_ids == {op.trace_id}  # ONE trace
        attempts = [r for r in sink if r["name"] == "score.attempt"]
        assert [r["attributes"]["attempt"] for r in attempts] == [1, 2]
        root = [r for r in sink if r["name"] == "score"][0]
        assert all(
            r["parentSpanId"] == root["spanId"] for r in attempts
        )
        assert root["attributes"]["attempts"] == 2

    def test_ids_are_unique(self):
        assert mint_trace_id() != mint_trace_id()
        assert len(mint_trace_id()) == 32
        assert len(mint_span_id()) == 16


class TestSpanExporter:
    def _record(self, i=0):
        return {
            "traceId": "t" * 32, "spanId": f"s{i}", "name": "x",
            "kind": "server", "startTimeUnixNano": 1, "durMs": 0.1,
        }

    def test_appends_jsonl_with_resource(self, tmp_path):
        with SpanExporter(str(tmp_path), service="svc") as ex:
            assert ex.export(self._record())
        lines = open(ex.path).read().splitlines()
        assert len(lines) == 1
        doc = json.loads(lines[0])
        assert doc["resource"]["service"] == "svc"
        assert doc["resource"]["pid"] == os.getpid()

    def test_byte_bound_drops_with_counter(self, tmp_path):
        # byte-bound enforcement happens on the WRITER side (export()
        # is an enqueue); close() drains, then the counters are exact
        drops = []
        ex = SpanExporter(
            str(tmp_path), max_bytes=200, on_drop=drops.append
        )
        try:
            for i in range(10):
                assert ex.export(self._record(i))  # accepted: queued
        finally:
            ex.close()
        n_written = len(open(ex.path).read().splitlines())
        assert 0 < n_written < 10
        assert ex.dropped == 10 - n_written
        assert set(drops) == {"bytes"}

    def test_rate_limit_drops_with_counter(self, tmp_path):
        clock = [0.0]
        ex = SpanExporter(
            str(tmp_path), max_per_s=2.0, clock=lambda: clock[0]
        )
        try:
            assert ex.export(self._record(0))
            assert ex.export(self._record(1))
            assert not ex.export(self._record(2))  # bucket empty
            clock[0] += 1.0  # refills 2 tokens
            assert ex.export(self._record(3))
        finally:
            ex.close()
        assert ex.dropped == 1

    def test_export_after_close_drops_never_raises(self, tmp_path):
        ex = SpanExporter(str(tmp_path))
        ex.close()
        ex.close()  # idempotent
        assert not ex.export(self._record())
        assert ex.dropped == 1

    def test_unencodable_record_drops(self, tmp_path):
        with SpanExporter(str(tmp_path)) as ex:
            ex.export({"spanId": object()})  # accepted; writer drops
        assert ex.dropped == 1

    def test_queue_bound_drops_at_enqueue(self, tmp_path):
        drops = []
        ex = SpanExporter(
            str(tmp_path), max_queue=2, on_drop=drops.append
        )
        # no writer races the queue check: stuff the queue before the
        # writer thread can drain by holding the condition
        with ex._cond:
            ex._queue.extend([self._record(0), self._record(1)])
        try:
            assert not ex.export(self._record(2))
        finally:
            ex.close()
        assert "queue" in drops

    def test_resolve_export_dir_rules(self, tmp_path, monkeypatch):
        monkeypatch.delenv("KOORD_TRACE_EXPORT", raising=False)
        assert resolve_export_dir(None) is None
        assert resolve_export_dir(False) is None
        assert resolve_export_dir(str(tmp_path)) == str(tmp_path)
        assert resolve_export_dir("1", state_dir="/sd") == "/sd/traces"
        assert resolve_export_dir("off") is None
        monkeypatch.setenv("KOORD_TRACE_EXPORT", str(tmp_path))
        assert resolve_export_dir(None) == str(tmp_path)
        # False must beat the env: the oracle/baseline opt-out
        assert resolve_export_dir(False) is None


def _span(trace, span, parent=None, name="x", kind="server",
          links=(), attrs=None, start=0):
    return {
        "traceId": trace, "spanId": span, "parentSpanId": parent,
        "name": name, "kind": kind,
        "startTimeUnixNano": start, "endTimeUnixNano": start + 1000,
        "durMs": 0.001, "status": {"code": "OK"},
        "attributes": dict(attrs or {}),
        "links": [
            {"traceId": lt, "spanId": ls, "type": LINK_FANIN}
            for lt, ls in links
        ],
    }


def _write_jsonl(path, spans):
    with open(path, "w") as fh:
        for s in spans:
            fh.write(json.dumps(s) + "\n")


class TestAssembler:
    def test_tree_and_cross_trace_links(self, tmp_path):
        # trace A: op -> attempt -> server -> launch; trace B's server
        # fan-in links to A's launch (the coalesced-batch shape)
        a, b = "a" * 32, "b" * 32
        _write_jsonl(tmp_path / "p1.jsonl", [
            _span(a, "op-a", name="score", kind="client"),
            _span(a, "att-a", "op-a", name="score.attempt",
                  kind="client", attrs={"server_span": "srv-a"}),
        ])
        _write_jsonl(tmp_path / "p2.jsonl", [
            _span(a, "srv-a", "att-a", name="score"),
            _span(a, "launch", "srv-a", name="score_launch",
                  kind="internal"),
            _span(b, "op-b", name="score", kind="client"),
            _span(b, "att-b", "op-b", kind="client",
                  attrs={"server_span": "srv-b"}),
            _span(b, "srv-b", "att-b", links=[(a, "launch")]),
        ])
        asm = assemble_mod.assemble([str(tmp_path)])
        assert set(asm.traces) == {a, b}
        assert not asm.orphan_spans
        assert not asm.client_orphans
        assert all(t.complete for t in asm.traces.values())
        tree_a = asm.traces[a]
        assert [s["spanId"] for s in tree_a.roots] == ["op-a"]
        assert [s["spanId"] for s in tree_a.children("srv-a")] == [
            "launch"
        ]

    def test_orphan_and_unresolved_flagged(self, tmp_path):
        t = "c" * 32
        _write_jsonl(tmp_path / "p.jsonl", [
            _span(t, "op", name="score", kind="client"),
            # parent never exported -> orphan
            _span(t, "lost", "ghost", kind="client"),
            # recorded server span nobody exported -> unresolved ref
            _span(t, "att", "op", kind="client",
                  attrs={"server_span": "missing"}),
        ])
        asm = assemble_mod.assemble([str(tmp_path)])
        tree = asm.traces[t]
        assert not tree.complete
        assert [s["spanId"] for s in tree.orphans] == ["lost"]
        assert [s["spanId"] for s in tree.unresolved] == ["att"]
        # both defects are client-kind: they count as client orphans
        assert {
            s["spanId"] for s in asm.client_orphans
        } == {"lost", "att"}

    def test_malformed_lines_counted_not_fatal(self, tmp_path):
        with open(tmp_path / "p.jsonl", "w") as fh:
            fh.write(json.dumps(_span("d" * 32, "s1")) + "\n")
            fh.write("{torn json line\n")
            fh.write(json.dumps({"no": "ids"}) + "\n")
        asm = assemble_mod.assemble([str(tmp_path)])
        assert asm.malformed_lines == 2
        assert len(asm.spans_by_id) == 1

    def test_waterfall_renders(self, tmp_path):
        t = "e" * 32
        _write_jsonl(tmp_path / "p.jsonl", [
            _span(t, "root", name="assign", kind="client"),
            _span(t, "child", "root", name="assign.attempt",
                  kind="client", start=200),
        ])
        asm = assemble_mod.assemble([str(tmp_path)])
        text = assemble_mod.render_waterfall(asm.traces[t])
        assert "assign [client]" in text
        assert "assign.attempt [client]" in text
        assert "INCOMPLETE" not in text

    def test_waterfall_without_device_attrs_unchanged(self, tmp_path):
        # the waterfall renders host spans only: no device annotations
        # anywhere (byte-stable vs pre-ISSUE-19 traces)
        t = "h" * 32
        _write_jsonl(tmp_path / "p.jsonl", [
            _span(t, "root", name="score", kind="client"),
            _span(t, "child", "root", name="score_launch", start=200),
        ])
        asm = assemble_mod.assemble([str(tmp_path)])
        text = assemble_mod.render_waterfall(asm.traces[t])
        assert "dev=" not in text
        assert "compile=" not in text
        assert "device" not in text.splitlines()[0]
        assert "=" not in text

    def test_cli_check_exit_codes(self, tmp_path, capsys):
        t = "f" * 32
        _write_jsonl(tmp_path / "ok.jsonl", [_span(t, "s1")])
        assert assemble_mod.main([str(tmp_path), "--check"]) == 0
        _write_jsonl(
            tmp_path / "bad.jsonl", [_span(t, "s2", parent="ghost")]
        )
        assert assemble_mod.main([str(tmp_path), "--check"]) == 1
        out = capsys.readouterr().out
        assert "orphan" in out


@pytest.fixture(scope="module")
def traced_tier(tmp_path_factory):
    """One in-process traced tier: server + client over UDS gRPC, a
    short traced stream (sync, score, memo-hit score, assign, memo-hit
    assign), exports assembled once for the assertions below."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from koordinator_tpu.bridge.client import ScorerClient
    from koordinator_tpu.bridge.server import ScorerServicer, make_server
    from koordinator_tpu.harness.trace import ClusterModel, TraceConfig
    from koordinator_tpu.harness.trace import _build_init
    import numpy as np

    td = tmp_path_factory.mktemp("traced-tier")
    traces = str(td / "traces")
    sock = os.path.join(str(td), "s.sock")
    sv = ScorerServicer(trace_export=traces)
    server = make_server(servicer=sv)
    server.add_insecure_port(f"unix://{sock}")
    server.start()
    client = ScorerClient(f"unix://{sock}", trace_export=traces)
    rng = np.random.default_rng(5)
    cfg = TraceConfig(nodes=8, pod_slots=24, gangs=2, gang_min_member=2)
    model = ClusterModel(_build_init(cfg, rng))
    try:
        client.sync(
            node_allocatable=model.nalloc, node_requested=model.nreq,
            node_usage=model.nuse, metric_fresh=list(model.fresh),
            pod_requests=model.preq, pod_estimated=model.pest,
            priority=list(model.priority), gang_id=list(model.gang_id),
            quota_id=list(model.quota_id),
            gang_min_member=list(model.gang_min),
            quota_runtime=model.qrt, quota_used=model.quse,
            quota_limited=model.qlim,
        )
        client.score_flat(top_k=4)
        client.score_flat(top_k=4)  # memo hit
        client.assign()
        client.assign()  # assign memo hit
    finally:
        client.close()
        sv.telemetry.close()
        server.stop(0)
    return sv, assemble_mod.assemble([traces])


class TestEndToEnd:
    def test_five_rpcs_five_complete_trees(self, traced_tier):
        _sv, asm = traced_tier
        assert len(asm.traces) == 5
        assert not asm.orphan_spans
        assert not asm.client_orphans
        assert all(t.complete for t in asm.traces.values())

    def test_coalesced_launch_fan_in_and_memo_link(self, traced_tier):
        _sv, asm = traced_tier
        launches = [
            s for s in asm.spans_by_id.values()
            if s["name"] == "score_launch"
        ]
        assert len(launches) == 1  # the memo hit launched nothing
        launch = launches[0]
        # both score RPC spans — the launcher AND the memo hit — link
        # to the ONE launch span, across trace boundaries
        score_servers = [
            s for s in asm.spans_by_id.values()
            if s["name"] == "score" and s["kind"] == "server"
        ]
        assert len(score_servers) == 2
        for s in score_servers:
            assert any(
                link["spanId"] == launch["spanId"]
                for link in s["links"]
            ), s
        memo_hits = [
            s for s in score_servers
            if s["attributes"].get("memo_hit")
        ]
        assert len(memo_hits) == 1
        assert memo_hits[0]["traceId"] != launch["traceId"]

    def test_assign_memo_links_to_owner_span(self, traced_tier):
        _sv, asm = traced_tier
        assigns = [
            s for s in asm.spans_by_id.values()
            if s["name"] == "assign" and s["kind"] == "server"
        ]
        assert len(assigns) == 2
        memo = [s for s in assigns if s["attributes"].get("memo_hit")]
        owner = [
            s for s in assigns if not s["attributes"].get("memo_hit")
        ]
        assert len(memo) == 1 and len(owner) == 1
        assert memo[0]["links"][0]["spanId"] == owner[0]["spanId"]

    def test_server_span_echo_recorded_on_attempts(self, traced_tier):
        _sv, asm = traced_tier
        attempts = [
            s for s in asm.spans_by_id.values()
            if s["name"].endswith(".attempt")
        ]
        assert len(attempts) == 5
        for att in attempts:
            ref = att["attributes"]["server_span"]
            assert ref in asm.spans_by_id
            assert asm.spans_by_id[ref]["kind"] == "server"

    def test_span_families_counted(self, traced_tier):
        sv, _asm = traced_tier
        from koordinator_tpu.obs.scorer_metrics import TRACE_SPANS

        text = sv.telemetry.registry.render()
        assert 'koord_scorer_trace_spans_total{kind="server"}' in text
        assert 'kind="internal"' in text

    def test_assign_cycle_record_carries_trace_id(self, traced_tier):
        sv, asm = traced_tier
        from koordinator_tpu.obs import validate_flight_dump

        records = sv.telemetry.flight.snapshot()
        with_trace = [
            r for r in records if r.get("trace_id")
        ]
        assert with_trace, "no cycle record carries a trace_id"
        assert all(
            r["trace_id"] in asm.traces for r in with_trace
        )
        # the grown schema validates what the recorder writes
        doc = sv.telemetry.flight.document("test")
        assert validate_flight_dump(doc) == []


class TestFlightDumpTraceIdSchema:
    def _doc(self, trace_id):
        return {
            "version": 1, "reason": "test", "dumped_at_unix": 1.0,
            "config": {}, "dropped_cycles": 0,
            "cycles": [{
                "cycle_id": "c1", "snapshot_id": None,
                "trace_id": trace_id, "started_unix": 1.0,
                "spans": [], "notes": {}, "error": None,
            }],
        }

    def test_null_and_string_accepted(self):
        from koordinator_tpu.obs import validate_flight_dump

        assert validate_flight_dump(self._doc(None)) == []
        assert validate_flight_dump(self._doc("t" * 32)) == []

    def test_non_string_rejected(self):
        from koordinator_tpu.obs import validate_flight_dump

        problems = validate_flight_dump(self._doc(42))
        assert any("trace_id" in p for p in problems)
        problems = validate_flight_dump(self._doc(["t"]))
        assert any("trace_id" in p for p in problems)
