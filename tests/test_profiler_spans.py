"""The served path on the profiler's clock (obs/spans.py ``koord.*``).

One delta Sync and one Assign run through the in-process
``SchedulerServer`` (gRPC over a UDS, ``ScorerClient``) inside a
``jax.profiler`` trace.  The trace must hold every stage annotation once
per RPC, each child inside its parent on the same thread, the server's
stages stamped with the cycle id the Assign answered with, and the
request-bytes counter must equal the serialized request's length.
"""

import glob
import os
import socket
import struct
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from koordinator_tpu.bridge.codegen import pb2  # noqa: E402
from koordinator_tpu.obs import spans as spans_mod  # noqa: E402

R = 13
BYTES = "koord_scorer_rpc_request_bytes_total"

CLIENT_SPANS = ("client.sync", "client.sync_encode")
SERVER_SPANS = (
    "sync", "sync_decode", "sync_apply", "delta_scatter", "sync_publish",
    "assign", "assign_queue", "dispatch", "readback", "assign_reply",
)
# (child, parent): every child lies inside its parent on one thread
NESTING = (
    ("client.sync_encode", "client.sync"),
    ("sync_decode", "sync"),
    ("sync_apply", "sync"),
    ("delta_scatter", "sync_apply"),
    ("sync_publish", "sync"),
    ("assign_queue", "assign"),
    ("dispatch", "assign"),
    ("readback", "assign"),
    ("assign_reply", "assign"),
)


def _cluster(rng, n_nodes=6, n_pods=16):
    alloc = rng.randint(8000, 64000, size=(n_nodes, R)).astype(np.int64)
    return {
        "node_allocatable": alloc,
        "node_requested": np.zeros((n_nodes, R), np.int64),
        "node_usage": rng.randint(0, 3000, (n_nodes, R)).astype(np.int64),
        "metric_fresh": [True] * n_nodes,
        "pod_requests": rng.randint(1, 2000, (n_pods, R)).astype(np.int64),
        "pod_estimated": rng.randint(1, 2000, (n_pods, R)).astype(np.int64),
        "priority": [int(v) for v in rng.randint(0, 9999, n_pods)],
        "gang_id": [-1] * n_pods,
        "quota_id": [-1] * n_pods,
    }


def _delta(state, rng):
    """The next cycle's Sync keywords: a few usage cells move."""
    usage = state["node_usage"].copy()
    usage[rng.randint(len(usage)), 1] += 7
    state["node_usage"] = usage
    return {k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in state.items() if k != "metric_fresh"}


def _koord_events(trace_dir):
    """{name: [(line key, start_ns, end_ns, stats)]} of the koord.*
    host events; a line of a host plane is one thread."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(spans_mod.PROFILER_PREFIX):
                    name = e.name[len(spans_mod.PROFILER_PREFIX):]
                    out.setdefault(name, []).append((
                        (plane.name, i), e.start_ns,
                        e.start_ns + e.duration_ns, dict(e.stats),
                    ))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from koordinator_tpu.bridge.client import ScorerClient
    from koordinator_tpu.scheduler.server import SchedulerServer

    work = str(tmp_path_factory.mktemp("profiled"))
    sock = os.path.join(work, "scorer.sock")
    server = SchedulerServer(
        lease_path=os.path.join(work, "leader.lease"), uds_path=sock,
        state_dir=os.path.join(work, "state"),
    ).start()
    deadline = time.monotonic() + 30
    while not server.elector.is_leader and time.monotonic() < deadline:
        time.sleep(0.02)
    assert server.elector.is_leader
    client = ScorerClient(f"unix://{sock}")
    rng = np.random.RandomState(5)
    state = _cluster(rng)
    sent = []
    stub = client._sync

    def recording_stub(request, timeout=None):
        sent.append(len(request.SerializeToString()))
        return stub(request, timeout=timeout)

    client._sync = recording_stub
    reg = server.servicer.telemetry.registry
    try:
        client.sync(**state)
        client.assign()
        # warm: the delta scatter's program compiles outside the trace
        client.sync(**_delta(state, rng))
        client.assign()
        before = {m: reg.get(BYTES, {"method": m}) or 0.0
                  for m in ("sync", "assign")}
        trace_dir = os.path.join(work, "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            client.sync(**_delta(state, rng))
            assignment, _status, _ms, _path = client.assign()
        finally:
            jax.profiler.stop_trace()
        after = {m: reg.get(BYTES, {"method": m}) or 0.0
                 for m in ("sync", "assign")}
        cycle_id = server.servicer.telemetry.flight.snapshot()[-1][
            "cycle_id"]
        yield {
            "events": _koord_events(trace_dir),
            "cycle_id": cycle_id,
            "sync_bytes": sent[-1],
            "delta_bytes": {m: after[m] - before[m] for m in after},
            "assignment": assignment,
            "server": server,
        }
    finally:
        client.close()
        server.stop()


@pytest.mark.parametrize("name", CLIENT_SPANS + SERVER_SPANS)
def test_each_span_once_per_rpc(traced, name):
    assert len(traced["events"].get(name, [])) == 1, sorted(
        traced["events"])


@pytest.mark.parametrize("child,parent", NESTING)
def test_child_inside_parent_on_its_thread(traced, child, parent):
    ((c_line, c_lo, c_hi, _),) = traced["events"][child]
    ((p_line, p_lo, p_hi, _),) = traced["events"][parent]
    assert c_line == p_line
    assert p_lo <= c_lo <= c_hi <= p_hi


@pytest.mark.parametrize(
    "name", [n for n in SERVER_SPANS if n != "sync_decode"])
def test_server_spans_carry_the_assign_cycle(traced, name):
    # sync_decode runs before its Sync's cycle exists; it nests in
    # koord.sync, which carries the cycle
    ((_, _, _, stats),) = traced["events"][name]
    assert stats.get("cycle") == traced["cycle_id"]


def test_client_and_server_run_on_different_threads(traced):
    (client,) = traced["events"]["client.sync"]
    (server,) = traced["events"]["sync"]
    assert client[0] != server[0]
    # the server's Sync body lies inside the client's call
    assert client[1] <= server[1] <= server[2] <= client[2]


def test_sync_request_bytes_equal_the_serialized_request(traced):
    assert traced["delta_bytes"]["sync"] == traced["sync_bytes"] > 0


def test_assign_request_bytes_counted(traced):
    req = pb2.AssignRequest(snapshot_id=traced["server"].servicer
                            .snapshot_id())
    # an Assign names the snapshot (its epoch and generation); the same
    # generation's id serializes to the same length
    assert traced["delta_bytes"]["assign"] == len(req.SerializeToString())


def test_traced_assign_places_pods(traced):
    assert (traced["assignment"] >= 0).any()


def test_raw_uds_request_bytes_counted(traced):
    server = traced["server"]
    reg = server.servicer.telemetry.registry
    before = reg.get(BYTES, {"method": "assign"}) or 0.0
    payload = pb2.AssignRequest(
        snapshot_id=server.servicer.snapshot_id()).SerializeToString()
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as conn:
        conn.connect(server.uds_path + ".raw")
        conn.sendall(struct.pack(">BI", 3, len(payload)) + payload)
        status, length = struct.unpack(">BI", conn.recv(5))
        while length:
            length -= len(conn.recv(length))
    assert status == 0
    after = reg.get(BYTES, {"method": "assign"}) or 0.0
    assert after - before == len(payload)


class TestAnnotationHelpers:
    def test_outside_a_session_profiled_is_null(self):
        ann = spans_mod.profiled("stage")
        with ann as inner:
            spans_mod.tag_cycle(inner, None)  # reads nothing
        assert ann is spans_mod._NULL_ANNOTATION

    def test_recorder_span_records_without_a_session(self):
        rec = spans_mod.SpanRecorder(epoch="e")
        with rec.span("dispatch"):
            pass
        (span,) = rec.commit()["spans"]
        assert span["name"] == "dispatch" and span["dur_ms"] is not None

    def test_recorder_span_is_annotated_with_its_cycle(self, tmp_path):
        rec = spans_mod.SpanRecorder(epoch="e")
        jax.profiler.start_trace(str(tmp_path))
        try:
            with rec.span("dispatch"):
                with spans_mod.profiled("inner", rec) as ann:
                    spans_mod.tag_cycle(ann, rec)
        finally:
            jax.profiler.stop_trace()
        cycle = rec.commit()["cycle_id"]
        events = _koord_events(str(tmp_path))
        ((line, lo, hi, stats),) = events["dispatch"]
        ((iline, ilo, ihi, istats),) = events["inner"]
        assert stats == {"cycle": cycle} and istats["cycle"] == cycle
        assert line == iline and lo <= ilo <= ihi <= hi

    def test_cycle_id_names_the_open_cycle(self):
        rec = spans_mod.SpanRecorder(epoch="e")
        assert not rec.has_pending()
        cycle = rec.cycle_id  # opens the cycle, as current() does
        assert rec.has_pending()
        assert rec.commit()["cycle_id"] == cycle
