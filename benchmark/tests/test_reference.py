"""The benchmark's reference against a second witness: the program's own
pure-Python oracle (koordinator_tpu/harness/reference.py), written
separately from it, on seeded states with quotas, stale metrics and
nodes past the LoadAware thresholds."""

import numpy as np
import pytest

from benchmark.harness import reference
from benchmark.harness.loop import Loop


def _state(tiny_cell, name, seed):
    cell = tiny_cell(name, nodes=40, backlog=200)
    loop = Loop(cell.config, cell.traffic, seed)
    loop.advance()
    state = {k: np.array(v, copy=True) for k, v in loop.state().items()}
    rng = np.random.default_rng(seed)
    state["metric_fresh"] = rng.random(40) > 0.2
    hot = rng.choice(40, 8, replace=False)
    state["node_usage"][hot, 0] = state["node_allocatable"][hot, 0] * 2 // 3
    return cell, state


def _oracle(state):
    from koordinator_tpu.harness.reference import ReferenceCycle

    quota = {}
    if "quota_runtime" in state:
        quota = dict(
            quota_runtime={i: r.tolist()
                           for i, r in enumerate(state["quota_runtime"])},
            quota_used={i: r.tolist()
                        for i, r in enumerate(state["quota_used"])},
            quota_limited={i: [bool(v) for v in r]
                           for i, r in enumerate(state["quota_limited"])})
    return ReferenceCycle(state["node_allocatable"].tolist(),
                          state["node_requested"].tolist(),
                          state["node_usage"].tolist(),
                          state["metric_fresh"].tolist(), **quota)


@pytest.mark.parametrize("name", ["colo2k.batch", "k8s5k.batch"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_assign_matches_the_program_oracle(tiny_cell, name, seed):
    cell, state = _state(tiny_cell, name, seed)
    want = _oracle(state).schedule_batch(
        state["pod_requests"].tolist(), state["pod_estimated"].tolist(),
        state["priority"].tolist(), state["quota_id"].tolist())
    got = reference.assign(state, cell.config["scoring"])
    assert got.tolist() == want
    assert (got >= 0).sum() > 0


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_score_list_matches_the_program_oracle(tiny_cell, seed):
    cell, state = _state(tiny_cell, "colo2k.batch", seed)
    oracle = _oracle(state)
    for pod in range(0, 200, 37):
        req = state["pod_requests"][pod].tolist()
        est = state["pod_estimated"][pod].tolist()
        qid = int(state["quota_id"][pod])
        ranked = sorted(
            (-oracle.combined_score(n, req, est), n)
            for n in range(40)
            if oracle.quota_ok(qid, req) and oracle.fit_ok(n, req)
            and oracle.loadaware_filter_ok(n))
        ni, sc = reference.score_list(state, cell.config["scoring"], pod)
        assert list(zip(ni.tolist(), sc.tolist())) == [
            (n, -s) for s, n in ranked]


# -- Strict Coscheduling, on hand-built states with known answers ---------

SCORING = {
    "usage_thresholds": {"cpu": 65, "memory": 95},
    "nonzero_milli_cpu": 100, "nonzero_memory_mib": 200}


def _hand(node_cpu, pods, quota_runtime=None):
    """Nodes of ``node_cpu`` milli-cores (memory ample, no usage) and
    pods ``(cpu, estimate cpu, priority, gang, quota)``."""
    n, p = len(node_cpu), len(pods)
    alloc = np.zeros((n, 13), np.int64)
    alloc[:, 0] = node_cpu
    alloc[:, 1] = 1 << 20
    req = np.zeros((p, 13), np.int64)
    est = np.zeros((p, 13), np.int64)
    req[:, 0] = [c[0] for c in pods]
    est[:, 0] = [c[1] for c in pods]
    state = {
        "node_allocatable": alloc,
        "node_requested": np.zeros_like(alloc),
        "node_usage": np.zeros_like(alloc),
        "metric_fresh": np.ones(n, bool),
        "pod_requests": req, "pod_estimated": est,
        "priority": np.array([c[2] for c in pods], np.int64),
        "gang_id": np.array([c[3] for c in pods], np.int64),
        "quota_id": np.array([c[4] for c in pods], np.int64)}
    if quota_runtime is not None:
        q = len(quota_runtime)
        state["quota_runtime"] = np.zeros((q, 13), np.int64)
        state["quota_runtime"][:, 0] = quota_runtime
        state["quota_used"] = np.zeros((q, 13), np.int64)
        state["quota_limited"] = np.zeros((q, 13), np.int64)
        state["quota_limited"][:, 0] = 1
    return state


def _without(state, rows):
    keep = np.setdiff1d(np.arange(len(state["priority"])), rows)
    pod_keys = ("pod_requests", "pod_estimated", "priority", "gang_id",
                "quota_id")
    return dict(state, **{k: state[k][keep] for k in pod_keys})


def test_failed_member_undoes_its_gang_and_frees_the_node():
    # gang 0: 3000m, then 5000m (fits no 4000m node), then 1000m; a
    # later 4000m pod needs a whole node, free only once gang 0 is undone
    state = _hand([4000, 4000], [(3000, 0, 9, 0, -1), (5000, 0, 9, 0, -1),
                                 (1000, 0, 9, 0, -1), (4000, 0, 5, -1, -1),
                                 (4000, 0, 5, -1, -1)])
    out, partway, _ = reference.run(state, SCORING)
    assert out.tolist() == [-1, -1, -1, 0, 1]
    assert partway == 1
    # placed one by one, with no gang, the first member keeps node 0
    free = reference.assign(dict(state, gang_id=np.full(5, -1)), SCORING)
    assert free.tolist() == [0, -1, 1, -1, -1]


def test_undone_gang_leaves_scores_as_if_absent():
    # tiny requests, large estimates: the gang's mark on the cycle is
    # LoadAware's assigned estimates; once undone, a later pod's scores
    # are those of the cycle without the gang
    pods = [(10, 3000, 9, 4, -1), (10, 3000, 9, 4, -1), (9000, 0, 9, 4, -1)]
    state = _hand([8000, 8000, 6000], pods)
    out, partway, cyc = reference.run(state, SCORING)
    assert out.tolist() == [-1, -1, -1] and partway == 1
    fresh = reference.Cycle(state, SCORING)
    np.testing.assert_array_equal(cyc.assigned_est, fresh.assigned_est)
    np.testing.assert_array_equal(cyc.requested, fresh.requested)
    probe_req, probe_est = np.zeros(13, np.int64), np.zeros(13, np.int64)
    probe_req[0], probe_est[0] = 500, 2000
    np.testing.assert_array_equal(cyc.scores(probe_req, probe_est),
                                  fresh.scores(probe_req, probe_est))
    # and a later pod goes where it goes with the gang absent
    later = _hand([8000, 8000, 6000], pods + [(500, 2000, 1, -1, -1)])
    got = reference.assign(later, SCORING)
    assert got[3] == reference.assign(_without(later, [0, 1, 2]),
                                      SCORING)[0]
    kept = reference.assign(dict(later, gang_id=np.full(4, -1)), SCORING)
    assert kept[3] != got[3]  # the estimates held would move it


def test_undone_gang_releases_its_quota():
    # quota 0 holds 5000m: gang 2's members take 2000 + 2000, the third
    # (2000) passes the cap; undone, a later 5000m pod of quota 0 fits
    state = _hand([8000, 8000], [(2000, 0, 9, 2, 0), (2000, 0, 9, 2, 0),
                                 (2000, 0, 9, 2, 0), (5000, 0, 5, -1, 0)],
                  quota_runtime=[5000])
    out, _, cyc = reference.run(state, SCORING)
    assert out.tolist() == [-1, -1, -1, 0]
    assert cyc.used[0, 0] == 5000
    _, _, undone = reference.run(_without(state, [3]), SCORING)
    np.testing.assert_array_equal(undone.used, state["quota_used"])


def test_gang_that_fits_binds_whole():
    state = _hand([4000, 4000], [(2000, 0, 9, 0, -1), (2000, 0, 9, 0, -1),
                                 (2000, 0, 9, 0, -1), (3000, 0, 5, -1, -1)])
    out, partway, cyc = reference.run(state, SCORING)
    assert out.tolist() == [0, 1, 0, -1] and partway == 0
    # the gang holds what it took: 2000m free on node 1 only
    assert cyc.requested[:, 0].tolist() == [4000, 2000]


def test_gang_free_state_takes_the_same_answers():
    state = _hand([4000, 4000], [(3000, 0, 9, -1, -1), (5000, 0, 9, -1, -1),
                                 (1000, 0, 9, -1, -1)])
    bare = {k: v for k, v in state.items() if k != "gang_id"}
    assert (reference.assign(state, SCORING).tolist()
            == reference.assign(bare, SCORING).tolist() == [0, -1, 1])
