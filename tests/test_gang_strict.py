"""Strict Coscheduling gangs: the scan and the dense kernel against the
benchmark's plain Strict reference (``benchmark/harness/reference.py``).

A member that finds no feasible node before its gang has min member
placed rejects the gang: every placement the gang made in the cycle is
undone, its remaining members are skipped, later pods see the freed
capacity; the reply gives the gang's members -1 (WAIT_GANG where placed
and released).  The clusters are the ``gang5k`` configuration cut to a
test's size, its memory-heavy draws making gangs fail partway.
"""

import copy
import json
import os

import numpy as np
import pytest

import koordinator_tpu  # noqa: F401  (x64)
from benchmark.harness import reference
from benchmark.harness.loop import Loop
from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.bridge.state import ResidentState, numpy_to_tensor
from koordinator_tpu.model import encode_snapshot
from koordinator_tpu.solver import (
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    STATUS_WAIT_GANG,
    greedy_assign,
    pallas_inputs_fit_i32,
)
from koordinator_tpu.solver.greedy import queue_order
from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GANG_REJECTED = "koord_scorer_gang_rejected_total"
GANG_RELEASED = "koord_scorer_gang_released_pods_total"


def _bench_json(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


def _gang_cell(nodes=24, backlog=320, quota=False):
    """gang5k and its mix, cut to ``nodes`` and ``backlog`` pods; with
    ``quota`` the gangs are spread over four ElasticQuota tenants."""
    config = copy.deepcopy(_bench_json("configs", "gang5k.json"))
    config["nodes"]["count"] = nodes
    if quota:
        config["quota"] = {"tenants": 4, "min_tenths": 2, "max_tenths": 4,
                           "shared_weight": [1, 4]}
    traffic = dict(_bench_json("traffic", "gangbatch.json"),
                   backlog_pods=backlog)
    return config, traffic


def _sync_request(state, gang_min):
    """The full Sync carrying ``state`` (the reference's view of it)."""
    req = pb2.SyncRequest()
    n = len(state["node_allocatable"])
    req.nodes.allocatable.CopyFrom(numpy_to_tensor(state["node_allocatable"]))
    req.nodes.requested.CopyFrom(numpy_to_tensor(state["node_requested"]))
    req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"]))
    req.nodes.metric_fresh.extend(bool(v) for v in state["metric_fresh"])
    req.nodes.names.extend(f"n{i}" for i in range(n))
    req.pods.requests.CopyFrom(numpy_to_tensor(state["pod_requests"]))
    req.pods.estimated.CopyFrom(numpy_to_tensor(state["pod_estimated"]))
    req.pods.names.extend(f"p{i}" for i in range(len(state["priority"])))
    req.pods.priority.extend(int(v) for v in state["priority"])
    req.pods.gang_id.extend(int(v) for v in state["gang_id"])
    req.pods.quota_id.extend(int(v) for v in state["quota_id"])
    req.gangs.min_member.extend(gang_min)
    if "quota_runtime" in state:
        req.quotas.runtime.CopyFrom(numpy_to_tensor(state["quota_runtime"]))
        req.quotas.used.CopyFrom(numpy_to_tensor(state["quota_used"]))
        req.quotas.limited.CopyFrom(numpy_to_tensor(state["quota_limited"]))
    return req


def _resident(state, gang_min):
    st = ResidentState()
    st.apply_sync(_sync_request(state, gang_min))
    return st


def _bound(result, n):
    a = np.asarray(result.assignment)[:n]
    return np.where(np.asarray(result.status)[:n] == STATUS_ASSIGNED, a, -1)


ENGINES = {
    "scan": lambda snap: greedy_assign(snap),
    "dense": lambda snap: greedy_assign_dense(snap, interpret=True),
}


@pytest.mark.parametrize("quota", [False, True])
@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_engines_match_strict_reference(seed, engine, quota):
    """(a) bound answers and post-cycle node requested, assigned
    estimates and quota used equal the reference's, over cycles of the
    harness loop in which gangs fail partway."""
    config, traffic = _gang_cell(quota=quota)
    loop = Loop(config, traffic, seed)
    m = config["gangs"]["members"]
    partway = 0
    for _ in range(3):
        loop.advance()
        state = loop.state()
        want, undone, cyc = reference.run(state, config["scoring"])
        partway += undone
        st = _resident(state, [m] * (len(want) // m))
        got = ENGINES[engine](st.snapshot())
        n, N = len(want), len(cyc.alloc)
        np.testing.assert_array_equal(_bound(got, n), want)
        np.testing.assert_array_equal(
            np.asarray(got.node_requested)[:N], cyc.requested)
        np.testing.assert_array_equal(
            np.asarray(got.node_estimated)[:N], cyc.assigned_est)
        if quota:
            Q = len(cyc.used)
            np.testing.assert_array_equal(
                np.asarray(got.quota_used)[:Q], cyc.used)
        loop.answer = want
    assert partway > 0, "no gang failed partway: the rollback was not hit"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Cycles of the harness loop through an in-process SchedulerServer
    over its UDS, each checked against the reference; returns the
    mismatches, the reference's gangs undone partway and members
    released, and the server's two gang counters."""
    from benchmark.run import start_server

    config, traffic = _gang_cell()
    loop = Loop(config, traffic, 2**31 + 17)
    kept = {}
    out = {"mismatch": 0, "cycles": 0, "partway": 0, "released": 0}
    server, client = start_server(str(tmp_path_factory.mktemp("gangsrv")))
    try:
        for _ in range(6):
            loop.cycle(client, keep=lambda s: kept.update(s=copy.deepcopy(s)))
            releases = []

            class Counting(reference.Cycle):
                def place(self, node, req, est, quota, sign=1):
                    releases.append(sign < 0)
                    return super().place(node, req, est, quota, sign)

            real = reference.Cycle
            reference.Cycle = Counting
            try:
                want, undone, _ = reference.run(kept["s"], config["scoring"])
            finally:
                reference.Cycle = real
            out["mismatch"] += int((loop.answer != want).sum())
            out["partway"] += undone
            out["released"] += sum(releases)
            out["cycles"] += 1
        reg = server.servicer.telemetry.metrics.registry
        out["rejected_total"] = reg.get(GANG_REJECTED, {}) or 0.0
        out["released_total"] = reg.get(GANG_RELEASED, {}) or 0.0
    finally:
        client.close()
        server.stop()
    return out


def test_served_path_matches_strict_reference(served):
    """(b) Sync then Assign over the UDS, several harness cycles: the
    bound answers equal the reference's."""
    assert served["cycles"] == 6
    assert served["mismatch"] == 0
    assert served["partway"] > 0


def test_gang_counters_match_reference(served):
    """(c) the server's counters: gangs rejected after placing members
    is the reference's count of gangs undone partway, released members
    the placements it undid."""
    assert served["rejected_total"] == served["partway"]
    assert served["released_total"] == served["released"]
    assert served["released"] >= served["partway"] > 0


def _one_node(cpu, members, min_member, extra=()):
    nodes = [{"name": "n", "allocatable": {"cpu": cpu, "memory": "64Gi",
                                           "pods": 110}, "usage": {}}]
    gangs = [{"name": "g", "min_member": min_member}]
    pods = [{"name": f"m{i}", "requests": {"cpu": "1", "pods": 1},
             "gang": "g", "priority": 5000} for i in range(members)]
    return encode_snapshot(nodes, pods + list(extra), gangs)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_satisfied_gang_keeps_its_members(engine):
    """(d) a gang that reached min member before a member fails stays
    placed; only the failing member is unschedulable."""
    snap = _one_node("2", members=3, min_member=2)
    got = ENGINES[engine](snap)
    a, s = np.asarray(got.assignment)[:3], np.asarray(got.status)[:3]
    np.testing.assert_array_equal(a, [0, 0, -1])
    np.testing.assert_array_equal(
        s, [STATUS_ASSIGNED, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE])


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_short_gang_places_none(engine):
    """(e) a gang with fewer valid members than min member never enters
    the cycle (PreFilter): none placed, the node untouched, and a pod
    after it takes the room."""
    lone = {"name": "solo", "requests": {"cpu": "1", "pods": 1},
            "priority": 1000}
    snap = _one_node("8", members=3, min_member=4, extra=[lone])
    got = ENGINES[engine](snap)
    a, s = np.asarray(got.assignment)[:4], np.asarray(got.status)[:4]
    np.testing.assert_array_equal(a, [-1, -1, -1, 0])
    assert (s[:3] == STATUS_UNSCHEDULABLE).all()
    cpu = np.asarray(got.node_requested)[0, 0]
    assert cpu == np.asarray(snap.nodes.requested)[0, 0] + 1000


def test_gang_straddling_kernel_blocks():
    """(f) a gang whose members sit in queue positions 124-131, across
    the dense kernel's 128-row blocks, fails at its last member: the
    rollback restores the checkpoint taken in the block before, every
    member reads -1, and a later gang fits only in the freed room."""
    filler = [{"name": f"f{i}", "requests": {"cpu": "100m", "pods": 1},
               "priority": 9000} for i in range(124)]
    big = [{"name": f"b{i}", "requests": {"cpu": "1", "pods": 1},
            "gang": "big", "priority": 5000} for i in range(8)]
    late = [{"name": f"l{i}", "requests": {"cpu": "3", "pods": 1},
             "gang": "late", "priority": 4000} for i in range(2)]
    nodes = [{"name": "n", "allocatable": {"cpu": "19400m", "memory": "64Gi",
                                           "pods": 1000}, "usage": {}}]
    gangs = [{"name": "big", "min_member": 8}, {"name": "late", "min_member": 2}]
    snap = encode_snapshot(nodes, filler + big + late, gangs)
    want, got = greedy_assign(snap), greedy_assign_dense(snap, interpret=True)
    for r in (want, got):
        a, s = np.asarray(r.assignment), np.asarray(r.status)
        assert (a[:124] == 0).all()
        assert (a[124:132] == -1).all()
        assert (s[124:131] == STATUS_WAIT_GANG).all()
        assert s[131] == STATUS_UNSCHEDULABLE
        assert (a[132:134] == 0).all()
        assert np.asarray(r.node_requested)[0, 0] == 124 * 100 + 2 * 3000
    for f in ("assignment", "status", "node_requested", "node_estimated"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)))


def test_queue_order_keeps_gangs_adjacent():
    """(g) a mixed-priority gang's members travel with its first member;
    the gang-free pods keep today's order among themselves, and without
    gang ids the order is today's."""
    prio = np.array([5, 9, 1, 7, 9, 3])
    gang = np.array([-1, 0, -1, 0, -1, -1], np.int32)
    valid = np.ones(6, bool)
    plain = np.asarray(queue_order(prio, valid))
    np.testing.assert_array_equal(plain, [1, 4, 3, 0, 5, 2])
    got = np.asarray(queue_order(prio, valid, gang, 1))
    np.testing.assert_array_equal(got, [1, 3, 4, 0, 5, 2])
    free = [p for p in got if gang[p] < 0]
    assert free == [p for p in plain if gang[p] < 0]
    # one priority and adjacent rows: the order does not move
    same = np.array([9, 9, 9, 5, 5, 1])
    gang2 = np.array([-1, 0, 0, 1, 1, -1], np.int32)
    np.testing.assert_array_equal(
        np.asarray(queue_order(same, valid, gang2, 2)),
        np.asarray(queue_order(same, valid)))


def _gang5k_state(est_mib=None, pods_slot=True):
    config, traffic = _gang_cell(nodes=500, backlog=5000)
    loop = Loop(config, traffic, 3)
    loop.advance()
    state = loop.state()
    if est_mib is not None:
        state["pod_estimated"][:, 1] = est_mib
        state["pod_requests"][:, 1] = est_mib
    if not pods_slot:
        state["pod_requests"][:, 3] = 0
    return state


@pytest.mark.parametrize("case,fits", [
    ("gang5k", True),
    # 111 estimates of 200,000 MiB on one node pass 2^31 / 100
    ("one_node_overflow", False),
    # no pod takes a pods slot: every pod's estimate may land on one node
    ("no_pods_slot", False),
])
def test_i32_gate_host_and_device_agree(case, fits):
    """(h) the host check on the bridge's mirrors and the device check
    on the snapshot agree; gang5k's sizes pass (its all-pods estimate
    sum is past the bound, its one-node bound well under it); a node
    whose accumulator would overflow fails."""
    state = {
        "gang5k": lambda: _gang5k_state(),
        "one_node_overflow": lambda: _gang5k_state(est_mib=200_000),
        "no_pods_slot": lambda: _gang5k_state(pods_slot=False),
    }[case]()
    if case == "gang5k":
        assert state["pod_estimated"][:, 1].sum() > 2**31 // 100
    st = _resident(state, [8] * 625)
    assert st.i32_fits() is fits
    assert pallas_inputs_fit_i32(st.snapshot()) is fits


def _gang_request(seed=5):
    config, traffic = _gang_cell()
    loop = Loop(config, traffic, seed)
    loop.advance()
    state = loop.state()
    return _sync_request(state, [8] * 40), state, config


def test_mesh_servicer_serves_gangs_off_the_shard_path():
    """(i) a mesh servicer sends a snapshot with gangs through run_cycle,
    not the round-based shard cycle, which has no rollback."""
    import jax

    from koordinator_tpu.parallel.mesh import cluster_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs virtual devices")
    req, state, config = _gang_request(seed=6)
    sv = ScorerServicer(mesh=cluster_mesh(jax.devices()[:2]))
    sv.sync(req)
    reply = sv.assign(pb2.AssignRequest(snapshot_id=sv.snapshot_id()))
    assert reply.path == "scan"
    a, s = np.asarray(reply.assignment), np.asarray(reply.status)
    want = reference.assign(state, config["scoring"])
    np.testing.assert_array_equal(np.where(s == STATUS_ASSIGNED, a, -1), want)
