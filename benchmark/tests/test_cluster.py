"""The benchmark's copy of the generators, estimator and quota division
yields config 4 exactly as the program's harness builds it, at seed 0."""

import json
import os

import numpy as np

from benchmark.harness import cluster as C

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _colo2k():
    with open(os.path.join(CONFIGS, "colo2k.json")) as fh:
        return json.load(fh)


def test_colo2k_seed0_is_quota_colocation():
    from koordinator_tpu.harness import generators
    from koordinator_tpu.harness.golden import build_sync_request
    from koordinator_tpu.bridge.state import tensor_to_numpy

    cfg = _colo2k()
    # the generator gives nodes 256 pods; colo2k takes the kubelet's 110
    assert cfg["nodes"]["pods"] == 110
    cfg["nodes"]["pods"] = 256
    rs = np.random.RandomState(0)
    nodes = C.make_nodes(rs, cfg["nodes"])
    pods = C.make_pods(rs, cfg["pods"], 10000, "tenant-pod")
    quotas = C.make_quotas(rs, cfg["quota"], nodes.alloc)
    pods.quota = np.arange(10000) % 16

    want, qids = build_sync_request(*generators.quota_colocation(seed=0))

    def t(msg):
        return tensor_to_numpy(msg, None)

    np.testing.assert_array_equal(nodes.alloc, t(want.nodes.allocatable))
    np.testing.assert_array_equal(nodes.usage, t(want.nodes.usage))
    np.testing.assert_array_equal(nodes.requested, t(want.nodes.requested))
    assert nodes.names == list(want.nodes.names)
    np.testing.assert_array_equal(pods.requests, t(want.pods.requests))
    np.testing.assert_array_equal(pods.priority, list(want.pods.priority))
    np.testing.assert_array_equal(pods.pclass, list(want.pods.priority_class))
    assert pods.names == list(want.pods.names)
    np.testing.assert_array_equal(pods.quota, qids)
    est = C.estimate(pods.requests, pods.limits, pods.pclass, cfg["scoring"])
    np.testing.assert_array_equal(est, t(want.pods.estimated))
    runtime, limited = C.quota_runtime(
        quotas, np.stack([pods.requests[pods.quota == q].sum(axis=0)
                          for q in range(16)]),
        nodes.alloc.sum(axis=0))
    np.testing.assert_array_equal(runtime, t(want.quotas.runtime))
    np.testing.assert_array_equal(limited, t(want.quotas.limited))


def test_quota_division_shares_what_is_left_by_weight():
    """Two groups over their min split the rest 1:3; one under its min
    keeps its request."""
    q = C.Quotas(np.zeros((3, C.R), np.int64), np.zeros((3, C.R), np.int64),
                 np.array([1, 3, 1]))
    q.min[:, C.CPU] = [100, 100, 100]
    q.max[:, C.CPU] = [1000, 1000, 1000]
    req = np.zeros((3, C.R), np.int64)
    req[:, C.CPU] = [900, 900, 50]
    total = np.zeros(C.R, np.int64)
    total[C.CPU] = 650
    runtime, limited = C.quota_runtime(q, req, total)
    # 650 - 100 - 100 - 50 = 400 left, split 100 : 300
    assert runtime[:, C.CPU].tolist() == [200, 400, 50]
    assert limited[:, C.CPU].tolist() == [1, 1, 1]
    assert not limited[:, C.MEMORY].any()


def test_gang_draws_one_per_gang():
    """One draw of (cpu, memory, BE coin, priority) per gang, repeated
    over its rows: the gangs of 8 rows read as 8 pods drawn alone."""
    spec = _colo2k()["pods"]
    pairs = ((C.make_pods(np.random.RandomState(5), spec, 64, "p", 8),
              C.make_pods(np.random.RandomState(5), spec, 8, "p")),
             (C.draw_pods(np.random.default_rng(5), spec, 64, "p", 0, 8),
              C.draw_pods(np.random.default_rng(5), spec, 8, "p", 0)))
    for pods, alone in pairs:
        for col, one in ((pods.requests, alone.requests),
                         (pods.limits, alone.limits),
                         (pods.priority, alone.priority),
                         (pods.pclass, alone.pclass)):
            np.testing.assert_array_equal(col, np.repeat(one, 8, axis=0))
        assert len(set(pods.names)) == 64
