"""Randomized cross-implementation parity fuzz.

Every feature dimension the cycle supports — quotas, gangs, stale
metrics, prod/aggregated LoadAware profiles, mixed priority bands — is
sampled randomly and every device path — the dense-layout Pallas kernel
(interpret, plain and its Strict-gang variant) and the round-based
shard_map wave path — must match the lax.scan oracle bit-for-bit on
assignments AND post-cycle state.  This is the drift alarm for the
invariant the framework maintains (scan / dense / waves, plus the C++
baseline in tests/test_native_bridge.py).

Strict gangs roll back only in the scan and the dense kernel
(solver.run_cycle and the bridge never send a gang to the shard path),
so the wave path meets each fuzzed input with its gang ids cleared.
"""

import dataclasses


import numpy as np
import pytest

from koordinator_tpu.config import AggregatedArgs, CycleConfig, LoadAwareArgs
from koordinator_tpu.constraints import build_quota_table_inputs
from koordinator_tpu.model import encode_snapshot, resources as res
from koordinator_tpu.model.snapshot import PERCENTILES
from koordinator_tpu.solver import greedy_assign
from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

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
    """``snap`` with every pod's gang id cleared: the input the engine
    without a Strict rollback (the shard path) is served."""
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
    """The dense kernel's Strict-gang variant, forced on for a gang-free
    input, runs every pod through its gang-free gate: placements and
    state equal the scan's."""
    snap, cfg = _fuzz_snapshot(seed)
    snap = _gang_free(snap)
    want = greedy_assign(snap, cfg)
    got = greedy_assign_dense(snap, cfg, interpret=True, has_gangs=True)
    _assert_matches(want, got, seed)


@pytest.mark.parametrize("seed", range(8))
def test_scan_dense_parity_fuzz(seed):
    """The dense-layout kernel holds the same fuzzed invariant."""
    snap, cfg = _fuzz_snapshot(seed)
    want = greedy_assign(snap, cfg)
    _assert_matches(want, greedy_assign_dense(snap, cfg, interpret=True), seed)


TAIL_ROWS = 256  # the pod bucket of the tail-skip cases: two kernel blocks


def _tail_snapshot(n, variant):
    """``n`` pods valid after PreFilter in a 256-row pod bucket, their rows
    shuffled among the padding's.  The "gang" variant heads the queue with
    a gang of 2 that PreFilter drops (min member 3) where the bucket has
    room, and ends it with a gang of 8 that one node's cpu rejects at its
    last member: at 129 valid pods the gang holds queue rows 121-128, so
    it straddles into the last valid block and is undone there."""
    rng = np.random.RandomState(n)
    gangs, quotas = [], []
    if variant == "gang":
        big = 8 if n >= 8 else 0
        fill = n - big
        pods = [{"name": f"f{i}", "requests": {"cpu": "100m", "pods": 1},
                 "priority": 9000 - i} for i in range(fill)]
        pods += [{"name": f"b{i}", "requests": {"cpu": "1", "pods": 1},
                  "gang": "big", "priority": 100} for i in range(big)]
        if n + 2 <= TAIL_ROWS:
            pods += [{"name": f"s{i}", "requests": {"cpu": "1", "pods": 1},
                      "gang": "short", "priority": 9999} for i in range(2)]
        gangs = [{"name": "big", "min_member": 8},
                 {"name": "short", "min_member": 3}]
        nodes = [{"name": "n", "allocatable": {
            "cpu": f"{fill * 100 + 7300}m", "memory": "64Gi", "pods": 1000},
            "usage": {}}]
    else:
        pods = [{"name": f"p{i}", "requests": {
                    "cpu": f"{int(rng.randint(200, 2000))}m",
                    "memory": int(rng.randint(1, 5)) * Gi, "pods": 1},
                 "priority": int(rng.randint(0, 4)) * 1000,
                 "quota": "q" if i % 5 == 0 else None} for i in range(n)]
        quotas = [{"name": "q", "runtime": {"cpu": "40"}, "used": {}}]
        # room for every pod the quota admits, so the last rows place
        nodes = [{"name": f"n{k}", "allocatable": {
            "cpu": "64", "memory": "128Gi", "pods": 110},
            "usage": {"cpu": f"{4 * k}", "memory": f"{8 * k}Gi"}}
            for k in range(8)]
    snap = encode_snapshot(nodes, pods, gangs, quotas, pod_bucket=TAIL_ROWS)
    perm = rng.permutation(TAIL_ROWS)
    shuffled = {
        f.name: getattr(snap.pods, f.name)[perm]
        for f in dataclasses.fields(snap.pods)
        if f.name != "names" and getattr(snap.pods, f.name) is not None
    }
    return dataclasses.replace(
        snap, pods=dataclasses.replace(snap.pods, **shuffled)
    )


@pytest.mark.parametrize("variant", ["plain", "gang", "extras"])
@pytest.mark.parametrize("n", [0, 1, 127, 128, 129, TAIL_ROWS - 1, TAIL_ROWS])
def test_dense_skips_padding_rows(n, variant):
    """The dense kernel runs the rows of the valid pods alone: at every
    valid count across its block edges, gang-free, Strict-gang and with
    an extras tile, it equals the scan on placements, statuses and the
    post-cycle state, every invalid row reads -1, and ``rounds`` counts
    the pod steps run, the pods valid after PreFilter."""
    from koordinator_tpu.constraints.gang import strict_gangs

    snap = _tail_snapshot(n, variant)
    extras = {}
    if variant == "extras":
        rng = np.random.RandomState(n)
        cells = (TAIL_ROWS, snap.nodes.allocatable.shape[0])
        extras = {"extra_mask": rng.rand(*cells) > 0.2,
                  "extra_scores": rng.randint(0, 50, cells).astype(np.int64)}
    want = greedy_assign(snap, **extras)
    got = greedy_assign_dense(
        snap, interpret=True, has_gangs=variant == "gang", **extras
    )
    for field in ("assignment", "status", "node_requested",
                  "node_estimated", "quota_used"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=field,
        )
    _, valid, _ = strict_gangs(
        snap.pods.gang_id, snap.pods.valid, snap.gangs.min_member
    )
    valid = np.asarray(valid)
    assert int(valid.sum()) == n
    assert int(np.asarray(got.rounds)) == n
    assignment = np.asarray(got.assignment)
    assert (assignment[~valid] == -1).all()
    if variant == "gang" and n >= 8:
        # the big gang was undone: none placed, the node holds the fill
        big = np.asarray(snap.pods.gang_id) == 0
        assert (assignment[big] == -1).all()
        cpu = np.asarray(got.node_requested)[0, 0]
        assert cpu == (n - 8) * 100


# the round-based sweep: the default width (32, 4) on one cluster
# family, then wave widths x candidate depths on two more, every feature
# dimension of _fuzz_snapshot sampled underneath
WAVE_GRID = [(1, 1), (8, 4), (32, 1), (32, 4)]
WAVE_FUZZ = (
    [(seed, 32, 4) for seed in range(100, 104)]
    + [(seed, w, m) for seed in range(200, 203) for w, m in WAVE_GRID]
    + [(seed, w, m) for seed in range(300, 302) for w, m in [(8, 4), (32, 1)]]
)


@pytest.mark.parametrize("seed,wave,top_m", WAVE_FUZZ)
def test_scan_waves_parity_fuzz(seed, wave, top_m):
    """The round-based sharded path holds it too, at every (wave, top_m)
    width (node_requested comes back node-sharded; quota/prod dimensions
    all sampled)."""
    import jax

    from koordinator_tpu.parallel import greedy_assign_waves, make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    snap, cfg = _fuzz_snapshot(seed)
    snap = _gang_free(snap)
    want = greedy_assign(snap, cfg)
    got, rounds = greedy_assign_waves(
        snap, make_mesh(), cfg, wave=wave, top_m=top_m
    )
    _assert_matches(want, got, seed)
    assert 1 <= rounds <= snap.pods.capacity


class TestWaveDirectedCases:
    """The adversarial shapes the certification argument must survive
    (ISSUE 3): gang minMember boundaries, quota exhaustion mid-wave, and
    total contention where every wave degrades to a single commit.  The
    wave cases run the round-based cycle on the test mesh."""

    def test_gang_minmember_boundary(self):
        """Gangs sized exactly at/below minMember: the Strict rollback's
        WAIT_GANG statuses must match the scan bit-for-bit through the
        dense kernel, the engine run_cycle serves a gang with on TPU."""
        from koordinator_tpu.harness import generators
        from koordinator_tpu.model import encode_snapshot

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
        from koordinator_tpu.parallel import greedy_assign_waves, make_mesh

        snap = generators.quota_colocation_snapshot(pods=96, nodes=8)[0]
        want = greedy_assign(snap)
        got, _ = greedy_assign_waves(snap, make_mesh(), wave=16, top_m=4)
        _assert_matches(want, got, "quota-mid-wave")

    @pytest.mark.parametrize("top_m", [1, 4])
    def test_all_pods_contending_for_one_node(self, top_m):
        """Worst case: one big node dominates scoring, every pod's top
        candidate is the same node, and each wave certifies exactly one
        commit — parity must hold and rounds approach pod count."""
        import jax

        from koordinator_tpu.model import encode_snapshot
        from koordinator_tpu.parallel import greedy_assign_waves, make_mesh

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
        got, rounds = greedy_assign_waves(
            snap, make_mesh(jax.devices()[:2]), wave=8, top_m=top_m
        )
        _assert_matches(want, got, f"contention-top{top_m}")
        # with top_m=1 the contended waves degrade toward one commit
        # per round; the point here is exactness, not speed
        assert rounds >= 1
        assert int((np.asarray(got.assignment) >= 0).sum()) == 24
