"""Solver parity: batched greedy vs the sequential CPU reference cycle."""

import numpy as np
import jax.numpy as jnp
import pytest

from koordinator_tpu.config import CycleConfig
from koordinator_tpu.constraints.gang import strict_gangs
from koordinator_tpu.harness import generators
from koordinator_tpu.harness.reference import ReferenceCycle
from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import encode_snapshot
from koordinator_tpu.solver import (
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    STATUS_WAIT_GANG,
    greedy_assign,
    score_cycle,
)
from koordinator_tpu.solver.greedy import queue_order

R = res.NUM_RESOURCES


def _reference_from_snapshot(snap, cfg=CycleConfig(), quotas=False):
    n = int(np.asarray(snap.nodes.valid).sum())
    quota_runtime = {}
    quota_used = {}
    quota_limited = {}
    if quotas:
        qvalid = np.asarray(snap.quotas.valid)
        for q in range(int(qvalid.sum())):
            quota_runtime[q] = [int(x) for x in np.asarray(snap.quotas.runtime[q])]
            quota_used[q] = [int(x) for x in np.asarray(snap.quotas.used[q])]
            quota_limited[q] = [bool(x) for x in np.asarray(snap.quotas.limited[q])]
    return ReferenceCycle(
        np.asarray(snap.nodes.allocatable[:n]),
        np.asarray(snap.nodes.requested[:n]),
        np.asarray(snap.nodes.usage[:n]),
        [bool(b) for b in np.asarray(snap.nodes.metric_fresh[:n])],
        cfg=cfg,
        quota_runtime=quota_runtime,
        quota_used=quota_used,
        quota_limited=quota_limited,
    )


def _assert_parity(snap, cfg=CycleConfig(), quotas=False):
    n_pods = int(np.asarray(snap.pods.valid).sum())
    n_nodes = int(np.asarray(snap.nodes.valid).sum())
    result = greedy_assign(snap, cfg)
    got = np.asarray(result.assignment)[:n_pods]

    cyc = _reference_from_snapshot(snap, cfg, quotas)
    want = cyc.schedule_batch(
        [[int(x) for x in row] for row in np.asarray(snap.pods.requests[:n_pods])],
        [[int(x) for x in row] for row in np.asarray(snap.pods.estimated[:n_pods])],
        priorities=[int(x) for x in np.asarray(snap.pods.priority[:n_pods])],
        quota_ids=[int(x) for x in np.asarray(snap.pods.quota_id[:n_pods])],
    )
    np.testing.assert_array_equal(got, want)
    # device post-cycle accounting matches the reference's
    np.testing.assert_array_equal(
        np.asarray(result.node_requested)[:n_nodes], np.asarray(cyc.requested)
    )
    np.testing.assert_array_equal(
        np.asarray(result.node_estimated)[:n_nodes], np.asarray(cyc.estimated)
    )
    return result


def test_spark_colocation_parity():
    nodes, pods, gangs, quotas = generators.spark_colocation()
    snap = encode_snapshot(nodes, pods, gangs, quotas)
    result = _assert_parity(snap)
    # all spark+nginx pods fit on a 3-node cluster
    n_pods = int(np.asarray(snap.pods.valid).sum())
    assert (np.asarray(result.status)[:n_pods] == STATUS_ASSIGNED).all()


def test_loadaware_joint_parity_small():
    nodes, pods, gangs, quotas = generators.loadaware_joint(seed=7, pods=120, nodes=24)
    snap = encode_snapshot(nodes, pods, gangs, quotas)
    _assert_parity(snap)


def test_score_cycle_matches_per_pod_score():
    nodes, pods, gangs, quotas = generators.loadaware_joint(seed=8, pods=40, nodes=16)
    snap = encode_snapshot(nodes, pods, gangs, quotas)
    cfg = CycleConfig()
    scores, feasible = score_cycle(snap, cfg)
    n_pods = int(np.asarray(snap.pods.valid).sum())
    n_nodes = int(np.asarray(snap.nodes.valid).sum())
    cyc = _reference_from_snapshot(snap, cfg)
    reqs = np.asarray(snap.pods.requests[:n_pods])
    ests = np.asarray(snap.pods.estimated[:n_pods])
    for p in range(n_pods):
        for n in range(n_nodes):
            want = cyc.combined_score(n, [int(x) for x in reqs[p]], [int(x) for x in ests[p]])
            assert int(scores[p, n]) == want, (p, n)


def test_unschedulable_when_no_capacity():
    nodes = [{"name": "tiny", "allocatable": {"cpu": "1", "memory": "1Gi"}, "usage": {}}]
    pods = [{"name": "big", "requests": {"cpu": "8", "memory": "8Gi"}}]
    snap = encode_snapshot(nodes, pods)
    result = greedy_assign(snap)
    assert int(result.assignment[0]) == -1
    assert int(result.status[0]) == STATUS_UNSCHEDULABLE


def test_priority_order_wins_contention():
    # One node with room for exactly one pod; higher priority pod gets it.
    nodes = [{"name": "n", "allocatable": {"cpu": "2", "memory": "4Gi"}, "usage": {}}]
    pods = [
        {"name": "low", "requests": {"cpu": "2"}, "priority": 5000},
        {"name": "high", "requests": {"cpu": "2"}, "priority": 9500},
    ]
    snap = encode_snapshot(nodes, pods)
    result = greedy_assign(snap)
    assert int(result.assignment[1]) == 0
    assert int(result.assignment[0]) == -1


def test_gang_wait_status():
    # gang of 3 but only capacity for 2 -> Strict: the third member
    # rejects the gang, the two placed are released (-1, WAIT_GANG) and
    # their requests leave the node
    nodes = [{"name": "n", "allocatable": {"cpu": "2", "memory": "16Gi"}, "usage": {}}]
    gangs = [{"name": "g", "min_member": 3}]
    pods = [
        {"name": f"m{i}", "requests": {"cpu": "1"}, "gang": "g", "priority": 5000}
        for i in range(3)
    ]
    snap = encode_snapshot(nodes, pods, gangs)
    result = greedy_assign(snap)
    status = np.asarray(result.status)[:3]
    assert (np.asarray(result.assignment)[:3] == -1).all()
    assert (status == STATUS_WAIT_GANG).sum() == 2
    assert (status == STATUS_UNSCHEDULABLE).sum() == 1
    np.testing.assert_array_equal(
        np.asarray(result.node_requested), np.asarray(snap.nodes.requested)
    )


def test_gang_satisfied_all_assigned():
    nodes = [{"name": "n", "allocatable": {"cpu": "8", "memory": "16Gi"}, "usage": {}}]
    gangs = [{"name": "g", "min_member": 3}]
    pods = [
        {"name": f"m{i}", "requests": {"cpu": "1"}, "gang": "g", "priority": 5000}
        for i in range(3)
    ]
    snap = encode_snapshot(nodes, pods, gangs)
    result = greedy_assign(snap)
    assert (np.asarray(result.status)[:3] == STATUS_ASSIGNED).all()


def test_quota_cap_blocks_overuse():
    nodes = [{"name": "n", "allocatable": {"cpu": "16", "memory": "64Gi"}, "usage": {}}]
    quotas = [{"name": "q", "runtime": {"cpu": "2"}, "used": {}}]
    pods = [
        {"name": f"p{i}", "requests": {"cpu": "1"}, "quota": "q", "priority": 5000}
        for i in range(4)
    ]
    snap = encode_snapshot(nodes, pods, quotas=quotas)
    result = _assert_parity(snap, quotas=True)
    assert (np.asarray(result.assignment)[:4] >= 0).sum() == 2


def test_quota_parity_randomized():
    nodes, pods, gangs, _ = generators.loadaware_joint(seed=9, pods=60, nodes=12)
    quotas = [
        {"name": "qa", "runtime": {"cpu": "40", "memory": "100Gi"}, "used": {}},
        {"name": "qb", "runtime": {"cpu": "2", "memory": "4Gi"}, "used": {}},
    ]
    for i, p in enumerate(pods):
        p["quota"] = "qa" if i % 2 else "qb"
    snap = encode_snapshot(nodes, pods, quotas=quotas)
    _assert_parity(snap, quotas=True)


class TestQuotaZeroRuntime:
    """A declared dimension whose fair-division runtime is 0 must reject,
    not fall open (quotav1.LessThanOrEqual missing-key=0 semantics)."""

    def test_zero_runtime_dimension_rejects(self):
        from koordinator_tpu.constraints import build_quota_table_inputs

        nodes = [
            {"name": "n0", "allocatable": {"cpu": "10", "memory": 8 * 1024**3}}
        ]
        pods = [
            {"name": "p0", "requests": {"cpu": "1"}, "quota": "starved", "priority": 5000}
        ]
        quotas = [{"name": "starved", "min": {"cpu": 0}, "max": {"cpu": 0}}]
        pod_reqs = [res.resource_vector(p["requests"]) for p in pods]
        total = res.resource_vector({"cpu": "10", "memory": 8 * 1024**3})
        qdicts = build_quota_table_inputs(quotas, pod_reqs, [0], total)
        # the declared cpu dim survives with runtime 0
        assert "cpu" in qdicts[0]["limited"]
        snap = encode_snapshot(nodes, pods, [], qdicts)
        result = greedy_assign(snap)
        assert int(np.asarray(result.assignment)[0]) == -1
        _assert_parity(snap, quotas=True)

    def test_encode_unknown_gang_and_quota_degrade(self):
        nodes = [{"name": "n0", "allocatable": {"cpu": "10", "memory": 8 * 1024**3}}]
        pods = [
            {
                "name": "p0",
                "requests": {"cpu": "1"},
                "gang": "not-synced",
                "quota": "not-synced",
            }
        ]
        snap = encode_snapshot(nodes, pods, [], [])
        assert int(np.asarray(snap.pods.gang_id)[0]) == -1
        assert int(np.asarray(snap.pods.quota_id)[0]) == -1
        result = greedy_assign(snap)
        assert int(np.asarray(result.assignment)[0]) == 0


def test_quota_table_round_trip_feasible():
    """Regression: build_quota_table_inputs must emit round-trippable
    quantities — raw axis-unit ints got re-parsed as bytes and divided by
    MiB again, collapsing every quota's memory runtime to ~1 MiB and
    rejecting all pods at the bench sizes (BASELINE config #4)."""
    import numpy as np

    from koordinator_tpu.constraints import build_quota_table_inputs
    from koordinator_tpu.harness import generators
    from koordinator_tpu.model import encode_snapshot, resources as res
    from koordinator_tpu.solver import greedy_assign

    nodes, pods, gangs, quotas = generators.quota_colocation(pods=64, nodes=16)
    pod_reqs = [res.resource_vector(p["requests"]) for p in pods]
    qidx = {q["name"]: i for i, q in enumerate(quotas)}
    qids = [qidx.get(p.get("quota"), -1) for p in pods]
    total = [0] * res.NUM_RESOURCES
    for n in nodes:
        v = res.resource_vector(n["allocatable"])
        total = [a + b for a, b in zip(total, v)]
    qdicts = build_quota_table_inputs(quotas, pod_reqs, qids, total)
    snap = encode_snapshot(nodes, pods, gangs, qdicts)
    mem = res.RESOURCE_INDEX[res.MEMORY]
    runtime_mem = int(np.asarray(snap.quotas.runtime)[0, mem])
    assert runtime_mem > 1024, f"memory runtime collapsed to {runtime_mem} MiB"
    result = greedy_assign(snap)
    assert int((np.asarray(result.assignment) >= 0).sum()) > 0


class TestPrelaunchKernelChoice:
    """run_cycle picks its kernel before launch from what the input
    shows (node bucket vs the kernels' VMEM limits, i32 bounds, extras
    magnitude).  Nothing catches a kernel failure: it raises."""

    @staticmethod
    def _on_tpu(monkeypatch, **kernels):
        """Route run_cycle down its TPU branch on the CPU test platform,
        with the named Pallas entry points replaced."""
        import jax

        from koordinator_tpu.solver import pallas_dense

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for name, fn in kernels.items():
            monkeypatch.setattr(pallas_dense, f"greedy_assign_{name}", fn)

    def test_kernel_fault_raises(self, monkeypatch):
        import pytest

        from koordinator_tpu.solver import run_cycle

        def boom(*a, **k):
            raise RuntimeError("kernel refused")

        self._on_tpu(monkeypatch, dense=boom)
        nodes, pods, gangs, quotas = generators.loadaware_joint(
            seed=2, pods=16, nodes=8
        )
        snap = encode_snapshot(nodes, pods, gangs, quotas)
        for cfg in (CycleConfig(), CycleConfig(enable_loadaware=False)):
            with pytest.raises(RuntimeError, match="kernel refused"):
                run_cycle(snap, cfg)

    def test_over_limit_bucket_takes_scan(self, monkeypatch):
        from koordinator_tpu import solver

        def never(*a, **k):
            raise AssertionError("an over-limit bucket reached a kernel")

        self._on_tpu(monkeypatch, dense=never)
        nodes, pods, gangs, quotas = generators.loadaware_joint(
            seed=3, pods=24, nodes=12
        )
        snap = encode_snapshot(nodes, pods, gangs, quotas,
                               node_bucket=256)
        monkeypatch.setattr(solver, "DENSE_MAX_NODES", 128)
        got = solver.run_cycle(snap)
        assert got.path == "scan"
        np.testing.assert_array_equal(
            np.asarray(got.assignment),
            np.asarray(greedy_assign(snap).assignment),
        )

    def test_oversized_extra_scores_take_scan(self, monkeypatch):
        """extra_scores past the kernel's i32 headroom (>= 2^29) send the
        cycle to the bit-identical scan before launch: run_cycle never
        raises for in-contract inputs."""
        from koordinator_tpu.solver import run_cycle

        def never(*a, **k):
            raise AssertionError("oversized extras reached the kernel")

        self._on_tpu(monkeypatch, dense=never)
        nodes, pods, gangs, quotas = generators.loadaware_joint(
            seed=9, pods=24, nodes=6
        )
        snap = encode_snapshot(nodes, pods, gangs, quotas)
        P = snap.pods.capacity
        N = snap.nodes.allocatable.shape[0]
        big = jnp.full((P, N), 2**29, jnp.int64)
        got = run_cycle(snap, extra_scores=big)
        assert got.path == "scan"
        want = greedy_assign(snap, extra_scores=big)
        np.testing.assert_array_equal(
            np.asarray(got.assignment), np.asarray(want.assignment)
        )

    def test_variant_limits(self):
        from koordinator_tpu.solver import (
            DENSE_MAX_NODES,
            DENSE_MAX_NODES_EXTRAS,
            dense_kernel_fits,
        )

        assert dense_kernel_fits(2000, False)
        assert dense_kernel_fits(DENSE_MAX_NODES, False)
        assert not dense_kernel_fits(DENSE_MAX_NODES + 1, False)
        assert dense_kernel_fits(DENSE_MAX_NODES_EXTRAS, True)
        assert not dense_kernel_fits(DENSE_MAX_NODES_EXTRAS + 1, True)


@pytest.mark.parametrize("gangs", [False, True])
@pytest.mark.parametrize("seed", range(3))
def test_queue_order_puts_valid_rows_first(seed, gangs):
    """Every valid row precedes every invalid one in the visit order, with
    invalid rows scattered among the valid in the input and, with gangs,
    a gang that PreFilter drops: the dense kernel runs the first
    ``sum(valid)`` rows of the order and skips the rest."""
    rng = np.random.RandomState(seed)
    P = 96
    priority = rng.randint(0, 4, P) * 1000
    valid = rng.rand(P) > 0.4
    order_args = ()
    if gangs:
        # gang 2 asks for more members than it has: PreFilter drops it
        min_member = np.array([2, 3, P, 1], np.int32)
        gang_id = rng.randint(-1, len(min_member), P).astype(np.int32)
        gang, after, _ = strict_gangs(gang_id, valid, min_member)
        after = np.asarray(after)
        assert (valid & ~after).any(), "no gang dropped by PreFilter"
        valid = after
        order_args = (gang, len(min_member))
    order = np.asarray(queue_order(priority, valid, *order_args))
    np.testing.assert_array_equal(np.sort(order), np.arange(P))
    in_order = valid[order]
    n = int(valid.sum())
    assert in_order[:n].all() and not in_order[n:].any()
