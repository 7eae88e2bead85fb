"""BatchedScorer sidecar: UDS round trip, delta sync, parity with the
in-process cycle (the bridge must be a transparent seam)."""

import os
import tempfile

import numpy as np
import pytest

from koordinator_tpu.bridge import ScorerClient, serve_uds
from koordinator_tpu.bridge.state import numpy_to_tensor, tensor_to_numpy
from koordinator_tpu.harness import generators
from koordinator_tpu.model import encode_snapshot, resources as res
from koordinator_tpu.model.snapshot import PriorityClass, estimate_pod
from koordinator_tpu.solver import run_cycle


@pytest.fixture()
def bridge():
    sock = os.path.join(tempfile.mkdtemp(), "scorer.sock")
    server = serve_uds(sock)
    client = ScorerClient(f"unix://{sock}")
    yield client
    client.close()
    server.stop(0)


def _tables(pods=24, nodes=6):
    nodes_l, pods_l, gangs, _ = generators.loadaware_joint(
        seed=11, pods=pods, nodes=nodes
    )
    nalloc = np.asarray([res.resource_vector(n["allocatable"]) for n in nodes_l])
    nuse = np.asarray([res.resource_vector(n.get("usage", {})) for n in nodes_l])
    preq = np.asarray([res.resource_vector(p["requests"]) for p in pods_l])
    # the client owns the estimator (the host scheduler computes estimates;
    # the sidecar scores whatever it is given) — mirror encode_snapshot
    pest = np.asarray(
        [
            estimate_pod(
                res.resource_vector(p["requests"]),
                res.resource_vector(p.get("limits", {})),
                PriorityClass.from_name(p.get("priority_class"))
                if p.get("priority_class") is not None
                else PriorityClass.from_priority_value(p.get("priority")),
            )
            for p in pods_l
        ]
    )
    return nodes_l, pods_l, gangs, nalloc, nuse, preq, pest


class TestBridge:
    def test_sync_assign_matches_inprocess(self, bridge):
        nodes_l, pods_l, gangs, nalloc, nuse, preq, pest = _tables()
        reply = bridge.sync(
            node_allocatable=nalloc,
            node_requested=np.zeros_like(nalloc),
            node_usage=nuse,
            node_names=[n["name"] for n in nodes_l],
            pod_requests=preq,
            pod_estimated=pest,
            pod_names=[p["name"] for p in pods_l],
            priority=[p.get("priority", 0) for p in pods_l],
        )
        assert reply.nodes == len(nodes_l) and reply.pods == len(pods_l)
        assignment, status, ms, path = bridge.assign()
        assert len(assignment) == len(pods_l)
        assert ms > 0
        # degraded-path visibility: the reply must name the device program
        assert path in ("pallas", "scan", "shard")

        # parity: the same cluster through the in-process entry point
        snap = encode_snapshot(
            [{**n, "requested": {}} for n in nodes_l], list(pods_l), [], []
        )
        direct = run_cycle(snap)
        direct_assign = np.asarray(direct.assignment)[: len(pods_l)]
        np.testing.assert_array_equal(assignment, direct_assign)

    def test_score_topk_sorted_and_feasible(self, bridge):
        nodes_l, pods_l, gangs, nalloc, nuse, preq, pest = _tables()
        bridge.sync(
            node_allocatable=nalloc,
            node_requested=np.zeros_like(nalloc),
            node_usage=nuse,
            pod_requests=preq,
            pod_estimated=pest,
        )
        lists = bridge.score(top_k=3)
        assert lists and all(len(entry) <= 3 for entry in lists)
        for entry in lists:
            scores = [s for _, s in entry]
            assert scores == sorted(scores, reverse=True)

    def test_delta_sync_updates_usage(self, bridge):
        nodes_l, pods_l, gangs, nalloc, nuse, preq, pest = _tables()
        bridge.sync(
            node_allocatable=nalloc,
            node_requested=np.zeros_like(nalloc),
            node_usage=nuse,
            pod_requests=preq,
            pod_estimated=pest,
        )
        a1, _, _, _ = bridge.assign()
        # warm cycle: bump usage on one node; client auto-encodes a delta
        nuse2 = nuse.copy()
        nuse2[0, res.RESOURCE_INDEX[res.CPU]] += 1000
        reply = bridge.sync(
            node_usage=nuse2,
            pod_requests=preq,
            pod_estimated=pest,
        )
        assert reply.nodes == len(nodes_l)
        a2, _, _, _ = bridge.assign()
        assert len(a2) == len(a1)

    def test_tensor_delta_roundtrip(self):
        prev = np.arange(64, dtype=np.int64).reshape(8, 8)
        nxt = prev.copy()
        nxt[3, 4] = 999
        t = numpy_to_tensor(nxt, prev)
        assert t.delta_idx and not t.data  # shipped as sparse delta
        back = tensor_to_numpy(t, prev)
        np.testing.assert_array_equal(back, nxt)

    def test_delta_out_of_range_raises_not_corrupts(self):
        """Client deltas cross a trust boundary: an index past the
        resident tensor must raise (the native delta_apply writes through
        raw pointers — unchecked it would corrupt server memory, not
        error)."""
        import pytest

        from koordinator_tpu.bridge.codegen import pb2

        base = np.zeros((2, 4), np.int64)
        t = pb2.Tensor(shape=[2, 4])
        t.delta_idx = np.asarray([99], "<i8").tobytes()
        t.delta_val = np.asarray([7], "<i8").tobytes()
        with pytest.raises(ValueError, match="out of range"):
            tensor_to_numpy(t, base)
        t.delta_idx = np.asarray([1, 2], "<i8").tobytes()
        with pytest.raises(ValueError, match="length mismatch"):
            tensor_to_numpy(t, base)

    def test_delta_shape_mismatch_rejected(self):
        """A stale differently-shaped mirror's indices may all land
        inside the resident cell count but write the wrong cells — shape
        equality must reject the frame outright."""
        import pytest

        from koordinator_tpu.bridge.codegen import pb2

        base = np.zeros((12, 13), np.int64)
        t = pb2.Tensor(shape=[8, 13])
        t.delta_idx = np.asarray([5], "<i8").tobytes()
        t.delta_val = np.asarray([7], "<i8").tobytes()
        with pytest.raises(ValueError, match="delta shape"):
            tensor_to_numpy(t, base)

    def test_rejected_sync_leaves_resident_state_untouched(self):
        """Half-applied syncs must not happen: a frame whose first
        tensor is valid but whose later tensor is rejected leaves the
        OTHER clients' delta baseline corrupted behind an unbumped
        generation.  apply_sync stages everything and commits only when
        the whole frame decodes."""
        import pytest

        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.harness import generators
        from koordinator_tpu.harness.golden import build_sync_request

        nodes_l, pods_l, _, _ = generators.loadaware_joint(
            seed=5, pods=16, nodes=4
        )
        req, _ = build_sync_request(nodes_l, pods_l, [], [])
        sv = ScorerServicer()
        sv.sync(req)
        before = sv.state.node_alloc.copy()

        bad = pb2.SyncRequest()
        # valid full allocatable replacement...
        bad.nodes.allocatable.shape.extend(before.shape)
        bad.nodes.allocatable.data = (before * 2).astype("<i8").tobytes()
        # ...but an out-of-range usage delta: the whole frame must bounce
        bad.nodes.usage.shape.extend(before.shape)
        bad.nodes.usage.delta_idx = np.asarray([10**6], "<i8").tobytes()
        bad.nodes.usage.delta_val = np.asarray([1], "<i8").tobytes()
        with pytest.raises(ValueError):
            sv.state.apply_sync(bad)
        np.testing.assert_array_equal(sv.state.node_alloc, before)

    def test_tensor_full_when_mostly_changed(self):
        prev = np.zeros((8, 8), np.int64)
        nxt = np.arange(64, dtype=np.int64).reshape(8, 8)
        t = numpy_to_tensor(nxt, prev)
        assert t.data and not t.delta_idx
        np.testing.assert_array_equal(tensor_to_numpy(t, None), nxt)


class TestFlatScoreReply:
    def test_flat_matches_legacy_lists(self):
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.harness import generators
        import numpy as np

        n, p, g, q = generators.loadaware_joint(seed=9, pods=64, nodes=16)
        req, _ = build_sync_request(n, p, [], [], node_bucket=16, pod_bucket=64)
        sv = ScorerServicer()
        sid = sv.sync(req).snapshot_id
        from koordinator_tpu.bridge.codegen import pb2

        legacy = sv.score(pb2.ScoreRequest(snapshot_id=sid, top_k=4))
        flat = sv.score(pb2.ScoreRequest(snapshot_id=sid, top_k=4, flat=True))
        pods_idx = np.frombuffer(flat.flat.pod_index, "<i4")
        counts = np.frombuffer(flat.flat.counts, "<i4")
        nidx = np.frombuffer(flat.flat.node_index, "<i4")
        scores = np.frombuffer(flat.flat.score, "<i8")
        assert counts.sum() == len(nidx) == len(scores)
        assert len(pods_idx) == len(legacy.pods)
        off = 0
        for entry, c in zip(legacy.pods, counts):
            assert list(entry.node_index) == nidx[off : off + c].tolist()
            assert list(entry.score) == scores[off : off + c].tolist()
            off += c
        assert flat.build_ms >= 0.0 and not flat.pods


class TestMultiChipServing:
    def test_mesh_backed_assign_matches_single_chip(self, tmp_path):
        """The production seam serves the round-based multi-chip cycle:
        a mesh-backed sidecar reports path="shard" and places pods
        bit-identically with a single-chip sidecar fed the same sync."""
        import jax

        if len(jax.devices()) < 8:
            import pytest

            pytest.skip("needs 8 (virtual) devices")
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.parallel import make_mesh

        nodes_l, pods_l, _, _ = generators.loadaware_joint(
            seed=21, pods=128, nodes=32
        )
        req, _ = build_sync_request(nodes_l, pods_l, [], [])

        sharded = ScorerServicer(mesh=make_mesh(jax.devices()[:8]))
        sid = sharded.sync(req).snapshot_id
        shard_reply = sharded.assign(pb2.AssignRequest(snapshot_id=sid))
        assert shard_reply.path == "shard"

        single = ScorerServicer()
        sid = single.sync(req).snapshot_id
        single_reply = single.assign(pb2.AssignRequest(snapshot_id=sid))
        assert list(shard_reply.assignment) == list(single_reply.assignment)
        assert list(shard_reply.status) == list(single_reply.status)

        # a 1-device mesh is honored too (path="shard", not silently
        # dropped): a dev box or degraded slice keeps the contract
        one = ScorerServicer(mesh=make_mesh(jax.devices()[:1]))
        sid = one.sync(req).snapshot_id
        one_reply = one.assign(pb2.AssignRequest(snapshot_id=sid))
        assert one_reply.path == "shard"
        assert list(one_reply.assignment) == list(single_reply.assignment)

    def test_shard_fault_fails_the_rpc(self, tmp_path, monkeypatch):
        """A shard-path fault fails the Assign RPC — no single-chip
        fallback hides it — and the next RPC attempts the shard path
        again (there is no demotion state to skip it)."""
        import jax
        import pytest

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        import koordinator_tpu.parallel as parallel
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.parallel import make_mesh

        nodes_l, pods_l, _, _ = generators.loadaware_joint(
            seed=4, pods=32, nodes=8
        )
        req, _ = build_sync_request(nodes_l, pods_l, [], [])
        sv = ScorerServicer(mesh=make_mesh(jax.devices()[:8]))
        sid = sv.sync(req).snapshot_id

        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            raise RuntimeError("wedged device")

        monkeypatch.setattr(parallel, "greedy_assign_waves", boom)
        for attempt in (1, 2):
            with pytest.raises(RuntimeError, match="wedged device"):
                sv.assign(pb2.AssignRequest(snapshot_id=sid))
            assert calls["n"] == attempt
        reg = sv.telemetry.registry
        assert reg.get(
            "koord_scorer_cycle_errors_total", {"stage": "assign"}
        ) == 2


class TestRawUdsReplyCap:
    def test_oversized_reply_errors_and_conn_survives(self, tmp_path, monkeypatch):
        """The server must refuse replies over the transport cap with a
        real error (every client enforces the same cap and would reject
        the frame as 'reply frame exceeds cap' otherwise) and keep the
        connection serving."""
        import socket
        import struct

        from koordinator_tpu.bridge import udsserver
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.harness import generators
        from koordinator_tpu.harness.golden import build_sync_request

        nodes_l, pods_l, _, _ = generators.loadaware_joint(
            seed=3, pods=32, nodes=8
        )
        req, _ = build_sync_request(nodes_l, pods_l, [], [])
        sock_path = str(tmp_path / "scorer.sock")
        server = udsserver.RawUdsServer(sock_path).start()

        def call(conn, method, payload):
            conn.sendall(struct.pack(">BI", method, len(payload)) + payload)
            head = conn.recv(5, socket.MSG_WAITALL)
            status, length = struct.unpack(">BI", head)
            body = b""
            while len(body) < length:
                body += conn.recv(length - len(body))
            return status, body

        try:
            c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            c.connect(sock_path)
            status, body = call(c, 1, req.SerializeToString())
            assert status == 0
            sid = pb2.SyncReply.FromString(body).snapshot_id
            # shrink the cap below any full-matrix Score reply
            monkeypatch.setattr(udsserver, "_MAX_FRAME", 64)
            score = pb2.ScoreRequest(snapshot_id=sid, top_k=0, flat=True)
            status, body = call(c, 2, score.SerializeToString())
            assert status == 1 and b"exceeds" in body
            # the connection is still serving after the refusal
            monkeypatch.setattr(udsserver, "_MAX_FRAME", 64 << 20)
            status, _ = call(c, 2, pb2.ScoreRequest(
                snapshot_id=sid, top_k=2, flat=True
            ).SerializeToString())
            assert status == 0
            c.close()
        finally:
            server.stop()
