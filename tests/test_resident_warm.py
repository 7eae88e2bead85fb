"""The warm-cycle fast path: device-resident snapshot + on-device deltas.

The exactness contract (ISSUE 1): after ANY sequence of warm syncs —
sparse deltas scattered into the resident device tensors, single-tensor
re-uploads, derived-column rebuilds — the resident snapshot must be
bit-identical in effect to a cold re-encode of the same logical state.
The fuzz here drives random delta/full/scalar/resize sequences through a
ScorerServicer and checks assignments AND scores against a cold oracle
on the scan path (and the interpret-mode Pallas kernel for a subset).

Also covered: the per-boot epoch in snapshot ids (a restarted sidecar
must never pass the delta-continuity check, ADVICE r5), companion-array
resets on table resizes, and the persistent compile cache (a second
process reuses the first's cache entry).
"""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.bridge.state import (
    ResidentState,
    _pc_column,
    numpy_to_tensor,
)
from koordinator_tpu.model.snapshot import PriorityClass

R = 13


def _full_sync_request(state: dict) -> "pb2.SyncRequest":
    """Encode the WHOLE logical state as one cold SyncRequest."""
    req = pb2.SyncRequest()
    req.nodes.allocatable.CopyFrom(numpy_to_tensor(state["node_alloc"]))
    req.nodes.requested.CopyFrom(numpy_to_tensor(state["node_requested"]))
    req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"]))
    req.nodes.metric_fresh.extend(bool(b) for b in state["node_fresh"])
    req.pods.requests.CopyFrom(numpy_to_tensor(state["pod_requests"]))
    req.pods.estimated.CopyFrom(numpy_to_tensor(state["pod_estimated"]))
    req.pods.priority.extend(int(v) for v in state["pod_priority"])
    req.pods.gang_id.extend(int(v) for v in state["pod_gang"])
    req.pods.quota_id.extend(int(v) for v in state["pod_quota"])
    req.gangs.min_member.extend(int(v) for v in state["gang_min"])
    if state["quota_runtime"] is not None:
        req.quotas.runtime.CopyFrom(numpy_to_tensor(state["quota_runtime"]))
        req.quotas.used.CopyFrom(numpy_to_tensor(state["quota_used"]))
        req.quotas.limited.CopyFrom(numpy_to_tensor(state["quota_limited"]))
    return req


def _random_state(rng, n_nodes, n_pods, with_quota):
    alloc = rng.randint(4000, 64000, size=(n_nodes, R)).astype(np.int64)
    state = {
        "node_alloc": alloc,
        "node_requested": rng.randint(0, 2000, (n_nodes, R)).astype(np.int64),
        "node_usage": rng.randint(0, 3000, (n_nodes, R)).astype(np.int64),
        "node_fresh": rng.rand(n_nodes) > 0.2,
        "pod_requests": rng.randint(1, 4000, (n_pods, R)).astype(np.int64),
        "pod_estimated": rng.randint(1, 4000, (n_pods, R)).astype(np.int64),
        "pod_priority": rng.randint(0, 9999, n_pods).astype(np.int64),
        "pod_gang": np.where(
            rng.rand(n_pods) > 0.5, rng.randint(0, 2, n_pods), -1
        ).astype(np.int32),
        "pod_quota": -np.ones(n_pods, np.int32),
        "gang_min": np.asarray([2, 3], np.int32),
        "quota_runtime": None,
        "quota_used": None,
        "quota_limited": None,
    }
    if with_quota:
        q = 3
        state["quota_runtime"] = rng.randint(
            5000, 500000, (q, R)
        ).astype(np.int64)
        state["quota_used"] = rng.randint(0, 4000, (q, R)).astype(np.int64)
        state["quota_limited"] = (rng.rand(q, R) > 0.5).astype(np.int64)
        state["pod_quota"] = np.where(
            rng.rand(n_pods) > 0.4, rng.randint(0, q, n_pods), -1
        ).astype(np.int32)
    return state


def _mutate(rng, state):
    """One warm step on the logical state; returns the SyncRequest that a
    delta-aware client would ship (changed tensors only, sparse where
    few cells moved) plus whether any node/pod resize happened."""
    req = pb2.SyncRequest()
    resized = False
    choice = rng.rand()
    if choice < 0.12:
        # resize the node table (full tensors, omitted companions)
        n_old = len(state["node_fresh"])
        n_new = int(rng.randint(3, 12))
        state["node_alloc"] = rng.randint(
            4000, 64000, (n_new, R)
        ).astype(np.int64)
        req.nodes.allocatable.CopyFrom(numpy_to_tensor(state["node_alloc"]))
        if n_new != n_old and rng.rand() < 0.4:
            # a resize frame may legally carry ONLY allocatable: the
            # server resets the omitted old-shaped requested/usage
            # mirrors to defaults of the new shape (zeros).  The client
            # has no acked baseline for them anymore — its next update
            # of those tensors must ship full, so flag them.
            state["node_requested"] = np.zeros((n_new, R), np.int64)
            state["node_usage"] = np.zeros((n_new, R), np.int64)
            state.setdefault("_no_baseline", set()).update(
                {"node_requested", "node_usage"}
            )
        else:
            state["node_requested"] = rng.randint(
                0, 2000, (n_new, R)
            ).astype(np.int64)
            state["node_usage"] = rng.randint(
                0, 3000, (n_new, R)
            ).astype(np.int64)
            req.nodes.requested.CopyFrom(
                numpy_to_tensor(state["node_requested"])
            )
            req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"]))
        if n_new != n_old:
            # row-count change: the server resets the omitted freshness
            # companion to its default (all fresh); an equal-size full
            # sync keeps the resident column (empty repeated = unchanged)
            state["node_fresh"] = np.ones(n_new, bool)
        resized = True
        return req, resized
    # sparse mutations on a random subset of tensors
    for key, wire in (
        ("node_requested", req.nodes.requested),
        ("node_usage", req.nodes.usage),
        ("pod_requests", req.pods.requests),
        ("pod_estimated", req.pods.estimated),
        ("quota_used", req.quotas.used),
    ):
        arr = state[key]
        if arr is None or rng.rand() > 0.55:
            continue
        prev = arr.copy()
        cells = rng.randint(1, max(2, arr.size // 8))
        flat = arr.reshape(-1)
        idx = rng.choice(arr.size, size=cells, replace=False)
        flat[idx] = rng.randint(0, 5000, cells)
        if key in state.get("_no_baseline", ()):
            # the server reset this mirror on a resize: no delta
            # baseline exists, the update must ride full once
            state["_no_baseline"].discard(key)
            prev = None
        wire.CopyFrom(numpy_to_tensor(arr, prev))
    if rng.rand() < 0.25:
        # scalar column churn: freshness and priorities
        state["node_fresh"] = rng.rand(len(state["node_fresh"])) > 0.2
        req.nodes.metric_fresh.extend(bool(b) for b in state["node_fresh"])
    if rng.rand() < 0.2:
        state["pod_priority"] = rng.randint(
            0, 9999, len(state["pod_priority"])
        ).astype(np.int64)
        req.pods.priority.extend(int(v) for v in state["pod_priority"])
    return req, resized


def _cold_oracle(state) -> ScorerServicer:
    sv = ScorerServicer()
    sv.sync(_full_sync_request(state))
    return sv


def _results(sv: ScorerServicer):
    """Cycle + score outputs over the VALID region.  Pad buckets are a
    physical detail the warm path may legitimately carry sticky across a
    shrink (avoiding a recompile) while a cold re-encode picks the
    smallest bucket — the exactness contract is over real rows/columns,
    where both must agree bit-for-bit."""
    from koordinator_tpu.solver import greedy_assign, score_cycle

    snap = sv.state.snapshot()
    N = int(np.asarray(snap.nodes.valid).sum())
    P = int(np.asarray(snap.pods.valid).sum())
    cycle = greedy_assign(snap)
    scores, feasible = score_cycle(snap)
    return (
        np.asarray(cycle.assignment)[:P],
        np.asarray(cycle.status)[:P],
        np.asarray(cycle.quota_used),
        np.asarray(scores)[:P, :N],
        np.asarray(feasible)[:P, :N],
        np.asarray(snap.pods.priority_class)[:P],
    )


class TestWarmParityFuzz:
    @pytest.mark.parametrize("seed", range(6))
    def test_delta_sequences_match_cold_reencode(self, seed):
        rng = np.random.RandomState(seed)
        state = _random_state(
            rng, n_nodes=int(rng.randint(4, 10)),
            n_pods=int(rng.randint(8, 24)),
            with_quota=bool(seed % 2),
        )
        warm = ScorerServicer()
        warm.sync(_full_sync_request(state))
        warm_seen = False
        for cycle in range(10):
            # materialize the resident snapshot so warm updates have a
            # target (a real server does this at the first Score/Assign)
            warm.state.snapshot()
            req, _resized = _mutate(rng, state)
            warm.sync(req)
            warm_seen = warm_seen or warm.state.last_sync_path == "warm"
            got = _results(warm)
            want = _results(_cold_oracle(state))
            for g, w, name in zip(
                got, want, ("assignment", "status", "quota_used",
                            "scores", "feasible", "priority_class")
            ):
                np.testing.assert_array_equal(
                    g, w, err_msg=f"seed={seed} cycle={cycle} {name}"
                )
            # the resident class column after the priority churn equals
            # the per-pod scalar derivation too, not only the cold build
            prio = state["pod_priority"]
            np.testing.assert_array_equal(
                got[-1], _scalar_pc_column(None, prio, len(prio), len(prio)),
                err_msg=f"seed={seed} cycle={cycle} scalar priority_class",
            )
        assert warm_seen, "fuzz never exercised the warm device path"

    @pytest.mark.parametrize("seed", (0, 1))
    def test_warm_resident_matches_cold_on_pallas_interpret(self, seed):
        """The resident-device snapshot feeds the Pallas kernel too: the
        interpret-mode kernel must produce the same placements from the
        warm-updated arrays as from a cold re-encode."""
        from koordinator_tpu.solver.pallas_cycle import greedy_assign_pallas

        rng = np.random.RandomState(100 + seed)
        state = _random_state(rng, n_nodes=6, n_pods=16, with_quota=True)
        warm = ScorerServicer()
        warm.sync(_full_sync_request(state))
        for _ in range(3):
            warm.state.snapshot()
            req, _ = _mutate(rng, state)
            warm.sync(req)
        warm_res = greedy_assign_pallas(
            warm.state.snapshot(), interpret=True
        )
        cold_res = greedy_assign_pallas(
            _cold_oracle(state).state.snapshot(), interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(warm_res.assignment), np.asarray(cold_res.assignment)
        )
        np.testing.assert_array_equal(
            np.asarray(warm_res.status), np.asarray(cold_res.status)
        )


class TestResidentMechanics:
    def _base_state(self):
        rng = np.random.RandomState(7)
        return _random_state(rng, n_nodes=4, n_pods=8, with_quota=False)

    def test_delta_sync_updates_device_in_place(self):
        state = self._base_state()
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        snap1 = sv.state.snapshot()
        assert sv.state.last_sync_path == "cold"

        prev = state["node_requested"].copy()
        state["node_requested"][0, 0] += 111
        req = pb2.SyncRequest()
        req.nodes.requested.CopyFrom(
            numpy_to_tensor(state["node_requested"], prev)
        )
        assert req.nodes.requested.delta_idx  # rode the wire as a delta
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"
        snap2 = sv.state.snapshot()
        # untouched tensors keep their resident device buffers
        assert snap2.nodes.allocatable is snap1.nodes.allocatable
        assert snap2.pods.requests is snap1.pods.requests
        # the touched one took the scatter
        got = np.asarray(snap2.nodes.requested)
        assert got[0, 0] == state["node_requested"][0, 0]

    def test_resize_drops_residency_and_resets_companions(self):
        """ADVICE r5: a full sync that changes the node/pod table size
        while omitting companion columns must reset them to defaults of
        the new shape — and the snapshot build must succeed."""
        state = self._base_state()
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        sv.state.snapshot()

        # grow nodes 4 -> 9 with ONLY the three node tensors on the wire
        rng = np.random.RandomState(8)
        grown = rng.randint(4000, 64000, (9, R)).astype(np.int64)
        req = pb2.SyncRequest()
        req.nodes.allocatable.CopyFrom(numpy_to_tensor(grown))
        req.nodes.requested.CopyFrom(
            numpy_to_tensor(np.zeros((9, R), np.int64))
        )
        req.nodes.usage.CopyFrom(numpy_to_tensor(np.zeros((9, R), np.int64)))
        sv.sync(req)
        assert sv.state.last_sync_path == "cold"
        # stale 4-row freshness column was reset, not left to fail here
        assert sv.state.node_fresh is None
        snap = sv.state.snapshot()
        assert snap.nodes.allocatable.shape[0] >= 9
        assert int(np.asarray(snap.nodes.valid).sum()) == 9

        # shrink pods 8 -> 3 omitting priorities/gangs/estimated: same deal
        preq = rng.randint(1, 4000, (3, R)).astype(np.int64)
        req = pb2.SyncRequest()
        req.pods.requests.CopyFrom(numpy_to_tensor(preq))
        sv.sync(req)
        assert sv.state.pod_priority is None
        assert sv.state.pod_estimated is None  # defaults to requests
        snap = sv.state.snapshot()
        assert int(np.asarray(snap.pods.valid).sum()) == 3
        np.testing.assert_array_equal(
            np.asarray(snap.pods.estimated)[:3], preq
        )

    def test_duplicate_delta_indices_rejected(self):
        """Duplicate flat indices must bounce the frame: host apply is
        sequential last-wins but device scatter duplicates are
        implementation-defined — accepting them could silently split the
        mirror from the resident tensors."""
        state = self._base_state()
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        bad = pb2.SyncRequest()
        bad.nodes.usage.shape.extend(state["node_usage"].shape)
        bad.nodes.usage.delta_idx = np.asarray([5, 5], "<i8").tobytes()
        bad.nodes.usage.delta_val = np.asarray([100, 200], "<i8").tobytes()
        before = sv.state.node_usage.copy()
        with pytest.raises(ValueError, match="duplicate"):
            sv.state.apply_sync(bad)
        np.testing.assert_array_equal(sv.state.node_usage, before)

    def test_resize_frame_with_stale_companion_tensor_rejected(self):
        """A resize frame carrying a companion tensor still shaped for
        the PRE-resize table (a delta validated against the old resident
        base, or an old-shaped full) must bounce whole — committing it
        would silently pad stale rows onto the new table."""
        state = self._base_state()
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        rng = np.random.RandomState(11)

        bad = pb2.SyncRequest()
        bad.nodes.allocatable.CopyFrom(
            numpy_to_tensor(rng.randint(4000, 64000, (9, R)).astype(np.int64))
        )
        # delta against the OLD 4-row requested mirror rides the same frame
        stale = state["node_requested"].copy()
        stale[0, 0] += 1
        bad.nodes.requested.CopyFrom(
            numpy_to_tensor(stale, state["node_requested"])
        )
        assert bad.nodes.requested.delta_idx
        before = sv.state.node_alloc.copy()
        with pytest.raises(ValueError, match="pre-resize"):
            sv.state.apply_sync(bad)
        np.testing.assert_array_equal(sv.state.node_alloc, before)

    def test_pod_resize_keeps_gang_table(self):
        """The gang table is per-gang, not per-pod: a pod-table resize
        frame that omits the unchanged gangs.min_member field must keep
        the resident gang table (a reset would silently disable gang
        gating while the new pods' gang_id column still references it)."""
        state = self._base_state()
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        before = sv.state.gang_min.copy()

        rng = np.random.RandomState(9)
        req = pb2.SyncRequest()
        req.pods.requests.CopyFrom(
            numpy_to_tensor(rng.randint(1, 4000, (5, R)).astype(np.int64))
        )
        req.pods.gang_id.extend([0, 0, 1, 1, 1])
        sv.sync(req)
        np.testing.assert_array_equal(sv.state.gang_min, before)
        snap = sv.state.snapshot()
        assert bool(np.asarray(snap.gangs.valid)[:2].all())
        np.testing.assert_array_equal(
            np.asarray(snap.gangs.min_member)[:2], before
        )

    def test_omitted_buckets_inherit_resident_bucket(self):
        """A warm frame without explicit buckets must not recompute a
        different pad bucket (that would reshape — and recompile — the
        resident snapshot mid-stream)."""
        state = self._base_state()
        req = _full_sync_request(state)
        req.node_bucket = 5  # explicit non-power-of-two cold bucket
        sv = ScorerServicer()
        sv.sync(req)
        sv.state.snapshot()
        assert sv.state.node_bucket == 5

        prev = state["node_usage"].copy()
        state["node_usage"][1, 2] += 7
        warm = pb2.SyncRequest()
        warm.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"], prev))
        sv.sync(warm)  # no bucket fields on the wire
        assert sv.state.node_bucket == 5
        assert sv.state.last_sync_path == "warm"


# every band edge of apis/extension/priority.go:84-101 and its class
_EDGE_CLASSES = {
    2999: PriorityClass.NONE, 3000: PriorityClass.FREE,
    3999: PriorityClass.FREE, 4000: PriorityClass.NONE,
    4999: PriorityClass.NONE, 5000: PriorityClass.BATCH,
    5999: PriorityClass.BATCH, 6000: PriorityClass.NONE,
    6999: PriorityClass.NONE, 7000: PriorityClass.MID,
    7999: PriorityClass.MID, 8000: PriorityClass.NONE,
    8999: PriorityClass.NONE, 9000: PriorityClass.PROD,
    9999: PriorityClass.PROD, 10000: PriorityClass.NONE,
}


def _scalar_pc_column(explicit, priority, P, pb):
    """The per-pod derivation ``_pc_column`` must equal, cell for cell."""
    col = np.full(pb, int(PriorityClass.NONE), np.int32)
    if explicit is not None:
        col[: len(explicit)] = explicit[:pb]
    else:
        for i in range(P):
            col[i] = int(PriorityClass.from_priority_value(int(priority[i])))
    return col


def _pc_case(name):
    """(explicit, priority, P, pb) for one parity case."""
    edges = np.asarray(list(_EDGE_CLASSES), np.int64)
    if name == "band-edges":
        return None, edges, len(edges), len(edges)
    if name == "zero-and-negative":
        prio = np.asarray([0, -1, -3000, -5500, -9999, -(2**63)], np.int64)
        return None, prio, len(prio), len(prio)
    if name == "past-int32":
        prio = np.asarray(
            [2**31 - 1, 2**31, 2**31 + 9500, 2**32 + 3000, -(2**31) - 1,
             2**40, 2**63 - 1], np.int64,
        )
        return None, prio, len(prio), len(prio)
    if name == "random-10k":
        prio = np.random.RandomState(24).randint(-2000, 12000, 10000)
        return None, prio.astype(np.int64), 10000, 16384
    if name == "padding":
        return None, edges[:5], 5, 8
    if name == "explicit":
        return np.asarray([0, 1, 2, 3, 4], np.int32), edges[:5], 5, 8
    raise ValueError(name)


class TestPriorityClassColumn:
    def test_bands_match_koordinator(self):
        for value, want in _EDGE_CLASSES.items():
            assert PriorityClass.from_priority_value(value) is want, value
        assert PriorityClass.from_priority_value(None) is PriorityClass.NONE

    @pytest.mark.parametrize("container", ("ndarray", "list"))
    @pytest.mark.parametrize("case", (
        "band-edges", "zero-and-negative", "past-int32", "random-10k",
        "padding", "explicit",
    ))
    def test_column_matches_scalar_derivation(self, case, container):
        explicit, prio, P, pb = _pc_case(case)
        if container == "list":
            prio = [int(v) for v in prio]
        got = _pc_column(explicit, prio, P, pb)
        want = _scalar_pc_column(explicit, prio, P, pb)
        assert got.dtype == np.int32 and got.shape == (pb,)
        np.testing.assert_array_equal(got, want)
        assert (got[P:] == int(PriorityClass.NONE)).all()


class TestEpochContinuity:
    def test_parse_snapshot_id_forms(self):
        from koordinator_tpu.bridge.plugin_sim import (
            generation,
            parse_snapshot_id,
        )

        assert parse_snapshot_id("sabc123-7") == ("abc123", 7)
        assert parse_snapshot_id("s42") == ("", 42)
        assert parse_snapshot_id("junk") == ("", -1)
        assert parse_snapshot_id("sabc-def") == ("abc", -1)
        assert generation("sabc123-7") == 7

    def test_server_epoch_in_snapshot_id_and_check(self):
        state = np.random.RandomState(3)
        s1, s2 = ScorerServicer(), ScorerServicer()
        assert s1._epoch != s2._epoch  # per-boot nonce
        st = _random_state(state, 4, 8, False)
        r1 = s1.sync(_full_sync_request(st))
        assert r1.snapshot_id == f"s{s1._epoch}-1"
        s1.assign(pb2.AssignRequest(snapshot_id=r1.snapshot_id))
        # a bare legacy "s<gen>" id is rejected: accepting it would
        # re-open the restart-coincidence hole for Score/Assign
        with pytest.raises(ValueError, match="not resident"):
            s1.assign(pb2.AssignRequest(snapshot_id="s1"))
        # a different boot's id is NOT resident here
        s2.sync(_full_sync_request(st))
        with pytest.raises(ValueError, match="not resident"):
            s2.assign(pb2.AssignRequest(snapshot_id=r1.snapshot_id))

    def test_restart_with_coincident_generation_forces_full_resync(self):
        """The trap the epoch closes (ADVICE r5): after a sidecar restart
        the generation counter restarts, so a foreign full sync can put
        the new boot EXACTLY at mirror.gen+1 for our next delta — the
        arithmetic check alone would silently land our deltas on the
        foreign baseline.  The epoch mismatch must force a full re-sync."""
        from koordinator_tpu.bridge.plugin_sim import GoPluginSim, NUM_AXES
        from koordinator_tpu.bridge.udsserver import RawUdsServer

        def vec(cpu=0, mem=0, pods=0):
            v = [0] * NUM_AXES
            v[0], v[1], v[3] = cpu, mem, pods
            return v

        alloc, reqv, pod = vec(8000, 16384, 110), vec(1000, 1024, 5), vec(500, 512, 1)
        nodes = [("node-a", alloc, reqv), ("node-b", alloc, reqv)]
        path = os.path.join(tempfile.mkdtemp(), "scorer.sock")
        srv = RawUdsServer(path).start()
        sim = GoPluginSim(path)
        sim.pre_score(nodes, "pod-1", pod)  # mirror: epoch A, gen 1
        assert sim.mirror.epoch and sim.mirror.gen == 1
        srv.stop()

        # fresh boot (epoch B); a FOREIGN client syncs a same-shaped but
        # different-valued node table, putting the new boot at gen 1
        srv2 = RawUdsServer(path).start()
        try:
            foreign = GoPluginSim(path)
            hot = vec(cpu=7777, mem=9999, pods=50)
            foreign.pre_score(
                [("node-a", alloc, hot), ("node-b", alloc, hot)],
                "foreign-pod", pod,
            )
            # our connection died with the old boot; reconnect cleanly so
            # the delta sync itself SUCCEEDS (the dangerous case — e.g. a
            # socket-activated listener keeps the dial working)
            sim._drop_client()
            sim.sent_frames.clear()
            scores = sim.pre_score(nodes, "pod-2", pod)
            # delta sync (gen 2 == mirror.gen+1 arithmetically!) + the
            # epoch-forced full re-sync + score
            methods = [m for m, _ in sim.sent_frames]
            assert methods == [1, 1, 2]
            assert sim.sent_frames[1][1] > sim.sent_frames[0][1]
            cold = GoPluginSim(path)
            assert cold.pre_score(nodes, "pod-2", pod) == scores
        finally:
            srv2.stop()


class TestRetraceBudget:
    """ISSUE 2: the warm path's compile economics, locked in at test
    time.  After one warm-up cycle has compiled every program the warm
    loop touches (the bucket-1 scatter, the cycle scan), a steady
    delta-Sync/Assign sequence must run with ZERO jit cache misses —
    any retrace means static metadata or geometry leaked into the trace
    signature (the PR-1 name-tuple bug class)."""

    def _warm_step(self, sv, state):
        prev = state["node_usage"].copy()
        state["node_usage"][0, 1] += 1
        req = pb2.SyncRequest()
        req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"], prev))
        assert req.nodes.usage.delta_idx  # rides as a sparse delta
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"
        return sv.assign(pb2.AssignRequest(snapshot_id=sv.snapshot_id()))

    def test_warm_sync_assign_sequence_is_retrace_free(self):
        from koordinator_tpu.analysis import retrace_guard

        rng = np.random.RandomState(21)
        state = _random_state(rng, n_nodes=5, n_pods=12, with_quota=False)
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        sv.state.snapshot()
        # warm-up cycle: compiles the delta scatter + the cycle program
        first = self._warm_step(sv, state)
        with retrace_guard(budget=0) as counter:
            for _ in range(4):
                reply = self._warm_step(sv, state)
        assert counter.traces == 0 and counter.compiles == 0
        assert len(reply.assignment) == len(first.assignment)

    def test_warm_sync_wave_assign_sequence_is_retrace_free(self):
        """The wave-batched cycle holds the same compile economics: with
        wave/top_m riding the STATIC CycleConfig, a warm delta-Sync +
        wave-Assign stream must hit zero jit cache misses after one
        warm-up cycle (a traced wave width would retrace every cycle —
        the hazard the koordlint rule rejects statically)."""
        from koordinator_tpu.analysis import retrace_guard
        from koordinator_tpu.config import CycleConfig

        rng = np.random.RandomState(23)
        state = _random_state(rng, n_nodes=5, n_pods=12, with_quota=False)
        sv = ScorerServicer(CycleConfig(wave=8, top_m=2))
        sv.sync(_full_sync_request(state))
        sv.state.snapshot()
        first = self._warm_step(sv, state)
        with retrace_guard(budget=0) as counter:
            for _ in range(4):
                reply = self._warm_step(sv, state)
        assert counter.traces == 0 and counter.compiles == 0
        assert reply.path == "wave"
        assert len(reply.assignment) == len(first.assignment)

    def test_warm_sequence_with_telemetry_enabled_is_retrace_free(self):
        """ISSUE 4 acceptance: telemetry is ALWAYS on in the servicer
        (spans, scorer metric families, flight ring), and the warm
        delta-Sync/Assign stream still holds ZERO jit cache misses —
        instrumentation must live entirely outside the traced programs.
        The same stream must actually POPULATE the telemetry: a
        zero-overhead subsystem that recorded nothing would pass
        vacuously."""
        import tempfile

        from koordinator_tpu.analysis import retrace_guard
        from koordinator_tpu.obs import validate_flight_dump

        rng = np.random.RandomState(29)
        state = _random_state(rng, n_nodes=5, n_pods=12, with_quota=False)
        sv = ScorerServicer(state_dir=tempfile.mkdtemp())
        sv.sync(_full_sync_request(state))
        sv.state.snapshot()
        self._warm_step(sv, state)  # warm-up compiles
        reg = sv.telemetry.registry
        miss_before = reg.get(
            "koord_scorer_jit_cache_miss_total", {"kind": "trace"}
        ) or 0
        with retrace_guard(budget=0) as counter:
            for _ in range(4):
                reply = self._warm_step(sv, state)
        assert counter.traces == 0 and counter.compiles == 0
        # the process-wide miss counter agrees with the guard: no new
        # misses landed during the telemetry-enabled warm stream
        miss_after = reg.get(
            "koord_scorer_jit_cache_miss_total", {"kind": "trace"}
        ) or 0
        assert miss_after == miss_before
        # ... and the stream populated the families + the flight ring
        count, _total = reg.get_histogram(
            "koord_scorer_cycle_latency_ms",
            {"path": reply.path, "wave": "1"},
        )
        assert count >= 4
        assert reg.get("koord_scorer_sync_total", {"kind": "delta"}) >= 4
        records = sv.telemetry.flight.snapshot()
        assert len(records) >= 4
        names = [s["name"] for s in records[-1]["spans"]]
        assert "sync_decode" in names and "delta_scatter" in names
        assert "dispatch" in names and "readback" in names
        # the ring dumps schema-valid under the guard's own contract
        assert validate_flight_dump(
            sv.telemetry.flight.document("test")
        ) == []

    def test_warm_stream_under_a_profiler_is_retrace_free(self, tmp_path):
        """The koord.* profiler annotations (obs/spans.py) stay outside
        the traced programs: a warm stream recorded under a profiler
        session holds zero jit cache misses, and its replies carry the
        same bytes as the same stream without one (timing and cycle-id
        fields aside)."""
        import jax

        from koordinator_tpu.analysis import retrace_guard

        def stream(profile: bool):
            rng = np.random.RandomState(31)
            state = _random_state(rng, n_nodes=5, n_pods=12,
                                  with_quota=False)
            sv = ScorerServicer()
            sv.sync(_full_sync_request(state))
            sv.state.snapshot()
            self._warm_step(sv, state)  # warm-up compiles
            replies = []
            if profile:
                jax.profiler.start_trace(str(tmp_path))
            try:
                with retrace_guard(budget=0) as counter:
                    for _ in range(3):
                        replies.append(self._warm_step(sv, state))
            finally:
                if profile:
                    jax.profiler.stop_trace()
            assert counter.traces == 0 and counter.compiles == 0
            for r in replies:
                r.cycle_ms = 0.0
                r.cycle_id = ""
            return [r.SerializeToString() for r in replies]

        assert stream(profile=True) == stream(profile=False)

    def test_warm_mesh_sharded_stream_is_retrace_free(self):
        """ISSUE 7 acceptance: the MESH-SHARDED resident snapshot keeps
        the zero-retrace invariant per shard.  A warm delta-Sync /
        Score / shard-Assign stream against a snapshot sharded over all
        8 forced-host devices must hit zero jit cache misses after one
        warm-up cycle — the shard-local scatter compiles once per
        (shape, bucket, mesh), the cross-shard top-M merge rides the
        static (cfg, mesh, wave, top_m) key, and the in/out sharding
        match means no hidden resharding programs are minted."""
        import jax

        from koordinator_tpu.analysis import retrace_guard
        from koordinator_tpu.parallel import cluster_mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        rng = np.random.RandomState(37)
        state = _random_state(rng, n_nodes=5, n_pods=12, with_quota=False)
        sv = ScorerServicer(
            mesh=cluster_mesh(jax.devices()), mesh_resident=True
        )
        sv.sync(_full_sync_request(state))
        snap = sv.state.snapshot()
        # really sharded: node rows split over all 8 devices
        assert len(snap.nodes.allocatable.sharding.device_set) == 8
        # warm-up compiles: sharded scatter, score/top_k, shard cycle
        sv.score(pb2.ScoreRequest(
            snapshot_id=sv.snapshot_id(), top_k=3, flat=True
        ))
        first = self._warm_step(sv, state)
        assert first.path == "shard"
        # ... and the shard-local incremental rescore (ISSUE 9): the
        # first WARM Score advances the resident score tensors through
        # the dirty-column kernel, whose compile belongs to warm-up
        sv.score(pb2.ScoreRequest(
            snapshot_id=sv.snapshot_id(), top_k=3, flat=True
        ))
        with retrace_guard(budget=0) as counter:
            for _ in range(4):
                prev = state["node_usage"].copy()
                state["node_usage"][0, 1] += 1
                req = pb2.SyncRequest()
                req.nodes.usage.CopyFrom(
                    numpy_to_tensor(state["node_usage"], prev)
                )
                sv.sync(req)
                assert sv.state.last_sync_path == "warm"
                sv.score(pb2.ScoreRequest(
                    snapshot_id=sv.snapshot_id(), top_k=3, flat=True
                ))
                reply = sv.assign(
                    pb2.AssignRequest(snapshot_id=sv.snapshot_id())
                )
        assert counter.traces == 0 and counter.compiles == 0
        assert reply.path == "shard"

    def test_warm_stream_with_coalesced_score_is_retrace_free(self):
        """ISSUE 5 acceptance: the coalescing dispatch engine is always
        on in the servicer, and a warm delta-Sync/Score/Assign stream
        through it still holds ZERO jit cache misses — the padded
        ``top_k`` launch (k padded to the sticky power-of-two bucket)
        must not mint new compiled shapes as batch composition varies,
        and the lock split must not reintroduce per-request retraces."""
        from koordinator_tpu.analysis import retrace_guard

        rng = np.random.RandomState(31)
        state = _random_state(rng, n_nodes=5, n_pods=12, with_quota=False)
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        sv.state.snapshot()
        # warm-up: compiles the scatter, the cycle, the score/top_k
        # programs (two top_k values land in the same pad bucket) AND
        # the incremental column rescore (ISSUE 9) — the first Score
        # after a warm delta advances the resident score tensors
        # through the dirty-column kernel
        sv.score(pb2.ScoreRequest(
            snapshot_id=sv.snapshot_id(), top_k=3, flat=True
        ))
        self._warm_step(sv, state)
        sv.score(pb2.ScoreRequest(
            snapshot_id=sv.snapshot_id(), top_k=2, flat=True
        ))
        with retrace_guard(budget=0) as counter:
            for step in range(4):
                prev = state["node_usage"].copy()
                state["node_usage"][0, 1] += 1
                req = pb2.SyncRequest()
                req.nodes.usage.CopyFrom(
                    numpy_to_tensor(state["node_usage"], prev)
                )
                sv.sync(req)
                assert sv.state.last_sync_path == "warm"
                # alternating k within one pad bucket: coalesced batch
                # composition changing must not change compiled shapes
                sv.score(pb2.ScoreRequest(
                    snapshot_id=sv.snapshot_id(),
                    top_k=3 if step % 2 else 2,
                    flat=True,
                ))
                sv.assign(pb2.AssignRequest(snapshot_id=sv.snapshot_id()))
        assert counter.traces == 0 and counter.compiles == 0
        assert sv.dispatch.stats()["batches"] >= 5

    def test_guard_actually_counts(self):
        """Negative control: a fresh jit inside the guard must trip it —
        otherwise a broken counter would pass the budget test vacuously."""
        import jax
        import jax.numpy as jnp

        from koordinator_tpu.analysis import (
            RetraceBudgetExceeded,
            retrace_guard,
        )

        with pytest.raises(RetraceBudgetExceeded, match="retrace budget"):
            with retrace_guard(budget=0) as counter:
                jax.jit(lambda x: x + 1)(jnp.zeros(3))
        assert counter.traces > 0

    def test_guard_is_inert_outside_the_block(self):
        import jax
        import jax.numpy as jnp

        from koordinator_tpu.analysis import retrace_guard

        with retrace_guard(budget=1) as counter:
            pass
        before = counter.traces
        jax.jit(lambda x: x - 1)(jnp.zeros(5))  # after stop(): not counted
        assert counter.traces == before


_CACHE_CHILD = r"""
import logging, os, sys
logging.basicConfig(stream=sys.stderr, level=logging.DEBUG)
logging.getLogger().setLevel(logging.WARNING)
logging.getLogger("jax._src.compiler").setLevel(logging.DEBUG)
import jax
jax.config.update("jax_platforms", "cpu")
import koordinator_tpu  # JAX_COMPILATION_CACHE_DIR set: the package sets none
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
import numpy as np
from koordinator_tpu.model import encode_snapshot
from koordinator_tpu.solver import greedy_assign

nodes = [
    {"name": f"n{i}", "allocatable": {"cpu": "8000m", "memory": 1 << 34}}
    for i in range(4)
]
pods = [
    {"name": f"p{i}", "requests": {"cpu": "500m", "memory": 1 << 30}}
    for i in range(8)
]
snap = encode_snapshot(nodes, pods, [], [])
print("ASSIGN", np.asarray(greedy_assign(snap).assignment).tolist())
"""


class TestCompileCacheSmoke:
    def test_second_process_reuses_cache_entry(self, tmp_path):
        """A restarted sidecar must skip the cycle compile: process one
        populates the persistent cache, process two must compile the
        cycle with zero persistent-cache misses and add no new entries."""
        cache = str(tmp_path / "xla-cache")
        env = dict(
            os.environ,
            JAX_COMPILATION_CACHE_DIR=cache,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
        )

        def run():
            return subprocess.run(
                [sys.executable, "-c", _CACHE_CHILD],
                capture_output=True, text=True, timeout=300, env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )

        p1 = run()
        assert p1.returncode == 0, p1.stderr[-2000:]
        assert "ASSIGN" in p1.stdout
        files1 = sorted(os.listdir(cache))
        assert files1, "first process wrote no cache entries"
        assert "CACHE MISS for 'jit_greedy_assign" in p1.stderr

        p2 = run()
        assert p2.returncode == 0, p2.stderr[-2000:]
        # same logical program -> same cache key: reused, not re-missed
        assert "CACHE MISS for 'jit_greedy_assign" not in p2.stderr
        files2 = sorted(os.listdir(cache))
        assert [f for f in files2 if f not in files1] == []
        assert p2.stdout.splitlines()[-1] == p1.stdout.splitlines()[-1]

    @pytest.mark.parametrize("env_dir", [None, "outside"])
    def test_cache_dir_rule(self, tmp_path, env_dir):
        """One rule: JAX_COMPILATION_CACHE_DIR, when set, is where the
        cache lives and the package sets nothing; unset, the cache goes
        to the fixed in-checkout path."""
        import koordinator_tpu

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        want = koordinator_tpu.XLA_CACHE_DIR
        if env_dir is not None:
            want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax, koordinator_tpu; "
             "print(jax.config.jax_compilation_cache_dir)"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want
