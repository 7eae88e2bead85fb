"""Randomized cross-implementation parity fuzz.

Every feature dimension the cycle supports — quotas, gangs, stale
metrics, prod/aggregated LoadAware profiles, mixed priority bands — is
sampled randomly and every device path — the wide Pallas kernel, the
dense-layout kernel (both interpret), and the round-based shard_map wave
path — must match the lax.scan oracle bit-for-bit on assignments AND
post-cycle state.  This is the drift alarm for the five-implementation
invariant the framework maintains (scan / wide / dense / waves, plus the
C++ baseline in tests/test_native_bridge.py).

Strict gangs roll back only in the scan and the dense kernel
(solver.run_cycle never sends a gang to the others), so the wide, wave
and shard engines meet each fuzzed input with its gang ids cleared.
"""

import dataclasses


import numpy as np
import pytest

from koordinator_tpu.config import AggregatedArgs, CycleConfig, LoadAwareArgs
from koordinator_tpu.constraints import build_quota_table_inputs
from koordinator_tpu.model import encode_snapshot, resources as res
from koordinator_tpu.model.snapshot import PERCENTILES
from koordinator_tpu.solver import greedy_assign
from koordinator_tpu.solver.pallas_cycle import greedy_assign_pallas

Gi = 1024 * 1024 * 1024


def _random_cluster(rng, n_nodes, n_pods, with_agg, with_prod):
    nodes = []
    for i in range(n_nodes):
        cpu = int(rng.choice([8000, 16000, 32000]))
        mem = int(rng.choice([32, 64, 128])) * Gi
        nd = {
            "name": f"n{i}",
            "allocatable": {"cpu": f"{cpu}m", "memory": mem, "pods": 110},
            "requested": {
                "cpu": f"{int(rng.randint(0, cpu // 2))}m",
                "memory": int(rng.randint(0, mem // 2)),
            },
            "usage": {
                "cpu": f"{int(rng.randint(0, cpu))}m",
                "memory": int(rng.randint(0, mem)),
            },
            "metric_fresh": bool(rng.rand() > 0.15),
        }
        if with_prod and rng.rand() > 0.3:
            nd["prod_usage"] = {
                "cpu": f"{int(rng.randint(0, cpu))}m",
                "memory": int(rng.randint(0, mem)),
            }
        if with_agg and rng.rand() > 0.3:
            nd["agg_usage"] = {
                pct: {
                    "cpu": f"{int(rng.randint(0, cpu))}m",
                    "memory": int(rng.randint(0, mem)),
                }
                for pct in PERCENTILES
                if rng.rand() > 0.25  # some percentiles missing
            }
        nodes.append(nd)

    pods = []
    bands = [("koord-prod", 9500), ("koord-mid", 7500), ("koord-batch", 5500)]
    for i in range(n_pods):
        pc, prio = bands[int(rng.randint(0, len(bands)))]
        pod = {
            "name": f"p{i}",
            "requests": {
                "cpu": f"{int(rng.randint(50, 4000))}m",
                "memory": int(rng.randint(1, 8)) * Gi // 2,
                "pods": 1,
            },
            "priority_class": pc,
            "priority": prio + int(rng.randint(0, 100)),
        }
        if rng.rand() > 0.5:
            pod["limits"] = {
                "cpu": f"{int(rng.randint(4000, 8000))}m",
                "memory": 8 * Gi,
            }
        pods.append(pod)

    gangs = []
    if rng.rand() > 0.5:
        n_gangs = int(rng.randint(1, 4))
        gangs = [
            {"name": f"g{k}", "min_member": int(rng.randint(2, 6))}
            for k in range(n_gangs)
        ]
        for i, p in enumerate(pods):
            if rng.rand() > 0.6:
                p["gang"] = f"g{i % n_gangs}"

    quotas = []
    if rng.rand() > 0.4:
        total_cpu = sum(
            res.parse_quantity(n["allocatable"]["cpu"], "cpu") for n in nodes
        )
        n_q = int(rng.randint(1, 5))
        for k in range(n_q):
            quotas.append(
                {
                    "name": f"q{k}",
                    "min": {"cpu": f"{total_cpu // (2 * n_q)}m"},
                    "max": {"cpu": f"{total_cpu // n_q}m"},
                    "shared_weight": int(rng.randint(1, 4)),
                    "used": {},
                }
            )
        for i, p in enumerate(pods):
            if rng.rand() > 0.4:
                p["quota"] = f"q{i % n_q}"
    return nodes, pods, gangs, quotas


def _random_cfg(rng, with_agg, with_prod):
    kwargs = {}
    if with_agg:
        kwargs["aggregated"] = AggregatedArgs(
            usage_thresholds={res.CPU: int(rng.randint(50, 95))},
            usage_aggregation_type=str(
                rng.choice(list(PERCENTILES))
            ),
            score_aggregation_type=str(
                rng.choice(list(PERCENTILES) + [""])
            ),
        )
    if with_prod:
        kwargs["prod_usage_thresholds"] = {res.CPU: int(rng.randint(40, 90))}
        kwargs["score_according_prod_usage"] = bool(rng.rand() > 0.5)
    la = LoadAwareArgs(**kwargs)
    return CycleConfig(
        loadaware=la,
        fit_scoring_strategy=str(
            rng.choice(["LeastAllocated", "MostAllocated"])
        ),
        fit_plugin_weight=int(rng.randint(1, 4)),
        loadaware_plugin_weight=int(rng.randint(1, 4)),
        enable_loadaware=bool(rng.rand() > 0.2),
    )


def _fuzz_snapshot(seed):
    rng = np.random.RandomState(seed)
    with_agg = bool(rng.rand() > 0.5)
    with_prod = bool(rng.rand() > 0.5)
    nodes, pods, gangs, quotas = _random_cluster(
        rng,
        n_nodes=int(rng.randint(4, 24)),
        n_pods=int(rng.randint(8, 64)),
        with_agg=with_agg,
        with_prod=with_prod,
    )
    qdicts = []
    qids = [-1] * len(pods)
    if quotas:
        pod_reqs = [res.resource_vector(p["requests"]) for p in pods]
        qidx = {q["name"]: i for i, q in enumerate(quotas)}
        qids = [qidx.get(p.get("quota"), -1) for p in pods]
        total = [0] * res.NUM_RESOURCES
        for n in nodes:
            v = res.resource_vector(n["allocatable"])
            total = [a + b for a, b in zip(total, v)]
        qdicts = build_quota_table_inputs(quotas, pod_reqs, qids, total)
    snap = encode_snapshot(nodes, pods, gangs, qdicts)
    cfg = _random_cfg(rng, with_agg, with_prod)
    return snap, cfg


def _gang_free(snap):
    """``snap`` with every pod's gang id cleared: the input the engines
    without a Strict rollback (wide, wave, shard) are served."""
    pods = dataclasses.replace(
        snap.pods, gang_id=np.full(snap.pods.gang_id.shape, -1, np.int32)
    )
    return dataclasses.replace(snap, pods=pods)


def _assert_matches(want, got, seed):
    np.testing.assert_array_equal(
        np.asarray(got.assignment), np.asarray(want.assignment), err_msg=f"seed={seed}"
    )
    np.testing.assert_array_equal(
        np.asarray(got.status), np.asarray(want.status)
    )
    np.testing.assert_array_equal(
        np.asarray(got.node_requested), np.asarray(want.node_requested)
    )
    np.testing.assert_array_equal(
        np.asarray(got.quota_used), np.asarray(want.quota_used)
    )


@pytest.mark.parametrize("seed", range(8))
def test_scan_pallas_parity_fuzz(seed):
    snap, cfg = _fuzz_snapshot(seed)
    snap = _gang_free(snap)
    want = greedy_assign(snap, cfg)
    _assert_matches(want, greedy_assign_pallas(snap, cfg, interpret=True), seed)


@pytest.mark.parametrize("seed", range(8))
def test_scan_dense_parity_fuzz(seed):
    """The dense-layout kernel holds the same fuzzed invariant."""
    from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

    snap, cfg = _fuzz_snapshot(seed)
    want = greedy_assign(snap, cfg)
    _assert_matches(want, greedy_assign_dense(snap, cfg, interpret=True), seed)


@pytest.mark.parametrize("seed", range(4))
def test_scan_waves_parity_fuzz(seed):
    """The round-based sharded path holds it too (node_requested comes
    back node-sharded; gang/quota/prod dimensions all sampled)."""
    import jax

    from koordinator_tpu.parallel import greedy_assign_waves, make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    seed = seed + 100  # distinct cluster family from the kernel fuzz
    snap, cfg = _fuzz_snapshot(seed)
    snap = _gang_free(snap)
    want = greedy_assign(snap, cfg)
    got, rounds = greedy_assign_waves(snap, make_mesh(), cfg)
    _assert_matches(want, got, seed)
    assert rounds >= 1


# the ISSUE-3 sweep: wave widths x candidate depths, every feature
# dimension of _fuzz_snapshot sampled underneath
WAVE_GRID = [(1, 1), (8, 4), (32, 1), (32, 4)]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("wave,top_m", WAVE_GRID)
def test_scan_wave_assign_parity_fuzz(seed, wave, top_m):
    """The single-chip wave path (solver/wave.py wave_assign) is
    bit-identical with the scan across the full random feature matrix,
    at every (wave, top_m) knob setting."""
    from koordinator_tpu.solver import wave_assign

    snap, cfg = _fuzz_snapshot(seed + 200)
    snap = _gang_free(snap)
    want = greedy_assign(snap, cfg)
    got = wave_assign(snap, cfg, wave=wave, top_m=top_m)
    _assert_matches(want, got, seed)
    rounds = int(np.asarray(got.rounds))
    assert 1 <= rounds <= snap.pods.capacity


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("wave,top_m", [(8, 4), (32, 1)])
def test_wave_pallas_parity_fuzz(seed, wave, top_m):
    """The wave Pallas kernel (interpret mode) holds the same fuzzed
    invariant through its i32 unpacked-key resolution."""
    snap, cfg = _fuzz_snapshot(seed + 300)
    snap = _gang_free(snap)
    cfg = dataclasses.replace(cfg, wave=wave, top_m=top_m)
    want = greedy_assign(snap, cfg)  # the scan ignores the wave knobs
    got = greedy_assign_pallas(snap, cfg, interpret=True)
    _assert_matches(want, got, seed)
    assert int(np.asarray(got.rounds)) >= 1


class TestWaveDirectedCases:
    """The adversarial shapes the certification argument must survive
    (ISSUE 3): gang minMember boundaries, quota exhaustion mid-wave, and
    total contention where every wave degrades to a single commit."""

    def test_gang_minmember_boundary(self):
        """Gangs sized exactly at/below minMember: the Strict rollback's
        WAIT_GANG statuses must match the scan bit-for-bit through the
        dense kernel, the engine run_cycle serves a gang with whatever
        wave width it asks (the wave path has no rollback)."""
        from koordinator_tpu.harness import generators
        from koordinator_tpu.model import encode_snapshot
        from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

        # 2 nodes x small gangs: some gangs land exactly minMember
        # members, some fall short and are released
        nodes, pods, gangs, quotas = generators.gang_batch(
            seed=3, pods=48, nodes=2, min_member=5
        )
        snap = encode_snapshot(nodes, pods, gangs, quotas)
        want = greedy_assign(snap)
        got = greedy_assign_dense(snap, interpret=True)
        _assert_matches(want, got, "gang-boundary")
        # the boundary is actually exercised: both statuses present
        status = np.asarray(got.status)[: len(pods)]
        assert (status == 2).any(), "no gang released; boundary not hit"
        assert (status == 0).any(), "no gang placed; boundary not hit"

    def test_quota_exhaustion_mid_wave(self):
        """Quotas sized to run dry midway through a wave: the blocked
        pods commit as unschedulable in-wave (node-invariant recheck)
        and quota accounting matches the scan exactly."""
        from koordinator_tpu.harness import generators
        from koordinator_tpu.solver import wave_assign

        snap = generators.quota_colocation_snapshot(pods=96, nodes=8)[0]
        want = greedy_assign(snap)
        got = wave_assign(snap, wave=16, top_m=4)
        _assert_matches(want, got, "quota-mid-wave")

    @pytest.mark.parametrize("top_m", [1, 4])
    def test_all_pods_contending_for_one_node(self, top_m):
        """Worst case: one big node dominates scoring, every pod's top
        candidate is the same node, and each wave certifies exactly one
        commit — parity must hold and rounds approach pod count."""
        from koordinator_tpu.model import encode_snapshot
        from koordinator_tpu.solver import wave_assign

        Gi2 = 1 << 30
        nodes = [
            {
                "name": "big",
                "allocatable": {"cpu": "64000m", "memory": 64 * Gi2,
                                "pods": 110},
            }
        ] + [
            {
                "name": f"tiny-{i}",
                "allocatable": {"cpu": "2000m", "memory": 2 * Gi2,
                                "pods": 110},
            }
            for i in range(7)
        ]
        pods = [
            {
                "name": f"p{i}",
                "requests": {"cpu": "900m", "memory": Gi2 // 2, "pods": 1},
            }
            for i in range(24)
        ]
        snap = encode_snapshot(nodes, pods, [], [])
        want = greedy_assign(snap)
        got = wave_assign(snap, wave=8, top_m=top_m)
        _assert_matches(want, got, f"contention-top{top_m}")
        rounds = int(np.asarray(got.rounds))
        # with top_m=1 the contended waves degrade toward one commit
        # per round; the point here is exactness, not speed
        assert rounds >= 1
        assert int((np.asarray(got.assignment) >= 0).sum()) == 24
