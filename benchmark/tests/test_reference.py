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
