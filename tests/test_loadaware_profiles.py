"""LoadAware aggregated-percentile + prod-usage profiles.

Reference semantics (``pkg/scheduler/plugins/loadaware/load_aware.go``):

* Filter :150-224 — with an AggregatedArgs profile, non-prod pods filter
  against the selected usage percentile and the profile's thresholds;
  nodes that reported no aggregates pass.
* Filter :226-258 — PriorityProd pods with ProdUsageThresholds filter
  against the node's prod-pods usage sum INSTEAD of whole-node usage.
* Score :291-327 — ScoreAccordingProdUsage scores prod pods against
  prod-pods usage; a score aggregation type scores everyone else against
  that percentile.

Three-way parity: lax.scan vs the sequential oracle (independent
implementation), the dense Pallas kernel (interpret) vs scan, shard_map
vs scan.
"""

import numpy as np
import pytest

import jax

from koordinator_tpu.config import AggregatedArgs, CycleConfig, LoadAwareArgs
from koordinator_tpu.harness.reference import ReferenceCycle
from koordinator_tpu.model import encode_snapshot, resources as res
from koordinator_tpu.model.snapshot import PERCENTILES
from koordinator_tpu.solver import greedy_assign, score_cycle
from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

Mi = 1024 * 1024
Gi = 1024 * Mi


def _cluster(seed=0, n_nodes=12, n_pods=48):
    """Mixed prod/batch pods on nodes with aggregated + prod usage data."""
    rng = np.random.RandomState(seed)
    nodes = []
    for i in range(n_nodes):
        cpu = 16000
        mem = 64 * Gi
        usage_cpu = int(rng.randint(1000, 12000))
        usage_mem = int(rng.randint(8, 56)) * Gi
        nd = {
            "name": f"n{i}",
            "allocatable": {"cpu": f"{cpu}m", "memory": mem, "pods": 110},
            "requested": {},
            "usage": {"cpu": f"{usage_cpu}m", "memory": usage_mem},
            "metric_fresh": i % 7 != 3,  # a few stale-metric nodes
            "prod_usage": {
                "cpu": f"{int(rng.randint(500, 11000))}m",
                "memory": int(rng.randint(4, 40)) * Gi,
            },
        }
        if i % 5 != 4:  # some nodes report no aggregates
            base = usage_cpu
            # every other reporting node carries only SOME percentiles
            # (the missing-cell fallback path: filter passes, score falls
            # back to plain NodeUsage)
            pcts = PERCENTILES if i % 2 == 0 else PERCENTILES[:2]
            nd["agg_usage"] = {
                pct: {
                    "cpu": f"{min(15000, base + 800 * k)}m",
                    "memory": min(60, 8 + 6 * k) * Gi,
                }
                for k, pct in enumerate(PERCENTILES)
                if pct in pcts
            }
        nodes.append(nd)
    pods = []
    for i in range(n_pods):
        prod = i % 3 == 0
        pods.append(
            {
                "name": f"p{i}",
                "requests": {
                    "cpu": f"{int(rng.randint(100, 1500))}m",
                    "memory": int(rng.randint(1, 4)) * Gi,
                    "pods": 1,
                },
                "priority_class": "koord-prod" if prod else "koord-batch",
                "priority": 9500 if prod else 5500,
            }
        )
    return nodes, pods


AGG_PROD_CFG = CycleConfig(
    loadaware=LoadAwareArgs(
        aggregated=AggregatedArgs(
            usage_thresholds={res.CPU: 70, res.MEMORY: 90},
            usage_aggregation_type="p95",
            score_aggregation_type="p90",
        ),
        prod_usage_thresholds={res.CPU: 55, res.MEMORY: 80},
        score_according_prod_usage=True,
    )
)

PROD_ONLY_CFG = CycleConfig(
    loadaware=LoadAwareArgs(
        prod_usage_thresholds={res.CPU: 55},
        score_according_prod_usage=True,
    )
)


def _oracle(nodes, cfg):
    agg = [
        {
            pct: res.resource_vector(nd["agg_usage"][pct])
            for pct in PERCENTILES
            if pct in nd["agg_usage"]
        }
        if "agg_usage" in nd
        else None
        for nd in nodes
    ]
    return ReferenceCycle(
        [res.resource_vector(nd["allocatable"]) for nd in nodes],
        [[0] * res.NUM_RESOURCES for _ in nodes],
        [res.resource_vector(nd["usage"]) for nd in nodes],
        [bool(nd.get("metric_fresh", True)) for nd in nodes],
        cfg=cfg,
        agg_usage=agg,
        prod_usage=[res.resource_vector(nd["prod_usage"]) for nd in nodes],
    )


@pytest.mark.parametrize("cfg", [AGG_PROD_CFG, PROD_ONLY_CFG])
class TestOracleParity:
    def test_scan_matches_oracle(self, cfg):
        nodes, pods = _cluster()
        snap = encode_snapshot(nodes, pods)
        result = greedy_assign(snap, cfg)
        got = np.asarray(result.assignment)[: len(pods)]

        oracle = _oracle(nodes, cfg)
        pe = np.asarray(snap.pods.estimated)
        want = oracle.schedule_batch(
            [res.resource_vector(p["requests"]) for p in pods],
            [pe[i].tolist() for i in range(len(pods))],
            priorities=[p["priority"] for p in pods],
            is_prod=[p["priority_class"] == "koord-prod" for p in pods],
        )
        np.testing.assert_array_equal(got, want)

    def test_score_cycle_matches_oracle(self, cfg):
        nodes, pods = _cluster(seed=3)
        snap = encode_snapshot(nodes, pods)
        scores, feasible = score_cycle(snap, cfg)
        scores = np.asarray(scores)
        feasible = np.asarray(feasible)
        oracle = _oracle(nodes, cfg)
        pe = np.asarray(snap.pods.estimated)
        for i, p in enumerate(pods):
            is_prod = p["priority_class"] == "koord-prod"
            req = res.resource_vector(p["requests"])
            for n in range(len(nodes)):
                want = oracle.combined_score(n, req, pe[i].tolist(), is_prod)
                assert int(scores[i, n]) == want, (i, n)
                want_ok = oracle.fit_ok(n, req) and oracle.loadaware_filter_ok(
                    n, is_prod
                )
                assert bool(feasible[i, n]) == want_ok, (i, n)


@pytest.mark.parametrize("cfg", [AGG_PROD_CFG, PROD_ONLY_CFG])
def test_pallas_matches_scan(cfg):
    nodes, pods = _cluster(seed=5, n_nodes=16, n_pods=64)
    snap = encode_snapshot(nodes, pods)
    want = np.asarray(greedy_assign(snap, cfg).assignment)
    got = np.asarray(
        greedy_assign_dense(snap, cfg, interpret=True).assignment
    )
    np.testing.assert_array_equal(got, want)


def test_shard_matches_scan():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from jax.sharding import Mesh

    from koordinator_tpu.parallel.shard_assign import greedy_assign_sharded

    nodes, pods = _cluster(seed=9, n_nodes=16, n_pods=64)
    snap = encode_snapshot(nodes, pods)
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("nodes",))
    want = np.asarray(greedy_assign(snap, AGG_PROD_CFG).assignment)
    got = np.asarray(
        greedy_assign_sharded(snap, mesh, AGG_PROD_CFG).assignment
    )
    np.testing.assert_array_equal(got, want)


def test_default_config_unaffected():
    """No aggregated/prod config -> identical to the legacy single-mask
    path (the prod-ok flag row equals the ok row)."""
    nodes, pods = _cluster(seed=11)
    for nd in nodes:
        nd.pop("agg_usage", None)
        nd.pop("prod_usage", None)
    snap = encode_snapshot(nodes, pods)
    a = np.asarray(greedy_assign(snap).assignment)
    b = np.asarray(greedy_assign_dense(snap, interpret=True).assignment)
    np.testing.assert_array_equal(a, b)


def test_prod_thresholds_without_prod_data_pass():
    """Config selects the prod branch; nodes with no prod metrics pass
    (filterProdUsage returns nil on empty PodsMetric, load_aware.go:227)
    even when whole-node usage exceeds the default thresholds."""
    nodes, pods = _cluster(seed=13, n_nodes=8, n_pods=16)
    for nd in nodes:
        nd.pop("prod_usage", None)
        nd.pop("agg_usage", None)
        nd["usage"] = {"cpu": "15000m", "memory": 60 * Gi}  # over thresholds
        nd["metric_fresh"] = True
    snap = encode_snapshot(nodes, pods)
    scores, feasible = score_cycle(snap, PROD_ONLY_CFG)
    feasible = np.asarray(feasible)
    for i, p in enumerate(pods):
        if p["priority_class"] == "koord-prod":
            assert feasible[i, : len(nodes)].any(), "prod pod must pass"
        else:
            assert not feasible[i, : len(nodes)].any(), "non-prod rejected"
