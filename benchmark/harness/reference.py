"""The plain sequential reference: Koordinator's per-pod scheduling cycle.

It answers the same questions as the served path, from the same synced
state, with none of its code: no JAX, no kernel, nothing imported from
the program.  It follows the published semantics the configuration
files state:

* Filter: NodeResourcesFit (requested + request <= allocatable on every
  resource the pod requests), LoadAware (a fresh node whose usage share
  of allocatable, rounded half away from zero, reaches a threshold is
  out; load_aware.go:173-224), ElasticQuota (used + request <= runtime
  on every limited dimension of the pod's quota).
* Score: NodeResourcesFit LeastAllocated over the non-zero request
  (weights cpu 1, memory 1) plus LoadAware leastRequested over usage +
  the estimates assigned in this cycle + the pod's estimate
  (load_aware.go:269-397), each plugin weighted 1.
* Assign: pods in queue order (priority descending, then row), each to
  its highest-scored feasible node, the lowest index on ties; its
  request and estimate then count on that node and its quota.
* Coscheduling, Strict mode (Koordinator's gang scheduling proposal,
  docs/proposals/scheduling/; coscheduling core.go rejectGangGroupById):
  where the state carries ``gang_id`` (a pod's gang, -1 for none), a
  gang member that finds no feasible node fails its whole gang.  PostFilter
  rejects the gang's waiting members and Unreserve releases what they
  reserved: every placement the gang made in this cycle is undone (node
  requested, the cycle's assigned estimates, quota used), so later pods
  see the freed capacity; the gang's schedule cycle is then invalid, so
  its remaining members fail PreFilter and are skipped.  A gang binds
  only with all its members placed (min member equal to the gang's
  size, the only kind a configuration declares).  A gang's members
  share a priority and sit in adjacent rows, so they are contiguous in
  the queue.  A pod with no gang (no ``gang_id`` in the state, or -1)
  is placed on its own, as above.
* Score lists: every feasible node with its score, highest first, the
  lowest index on ties.

Vectorised over nodes with numpy int64, sequential over pods.
"""

from __future__ import annotations

import numpy as np

MAX_NODE_SCORE = 100
CPU, MEMORY = 0, 1


class Cycle:
    """One cycle's state: the node table and, where given, the quota
    table.  ``scoring`` is the configuration's ``scoring`` block."""

    def __init__(self, state, scoring):
        self.alloc = np.array(state["node_allocatable"], np.int64)
        self.requested = np.array(state["node_requested"], np.int64)
        self.usage = np.array(state["node_usage"], np.int64)
        self.fresh = np.asarray(state["metric_fresh"], bool)
        self.assigned_est = np.zeros_like(self.alloc)
        self.runtime = state.get("quota_runtime")
        self.limited = state.get("quota_limited")
        self.used = (None if state.get("quota_used") is None
                     else np.array(state["quota_used"], np.int64))
        self.s = scoring
        thr = scoring["usage_thresholds"]
        ok = np.ones(len(self.alloc), bool)
        for r, t in ((CPU, thr["cpu"]), (MEMORY, thr["memory"])):
            total = self.alloc[:, r]
            pct = np.floor(self.usage[:, r] / np.where(total == 0, 1, total)
                           * 100 + 0.5)
            ok &= (total == 0) | (pct < t)
        # a node without a fresh metric passes LoadAware's Filter
        self.loadaware_ok = ok | ~self.fresh

    @staticmethod
    def _least(requested, capacity):
        """leastRequestedScore: (capacity - requested) * 100 / capacity,
        0 where capacity is 0 or exceeded."""
        cap = np.where(capacity == 0, 1, capacity)
        score = (capacity - requested) * MAX_NODE_SCORE // cap
        return np.where((capacity == 0) | (requested > capacity), 0, score)

    def scores(self, req, est):
        """Combined score of every node for one pod."""
        nz = req.copy()
        if nz[CPU] == 0:
            nz[CPU] = self.s["nonzero_milli_cpu"]
        if nz[MEMORY] == 0:
            nz[MEMORY] = self.s["nonzero_memory_mib"]
        fit = (self._least(self.requested[:, CPU] + nz[CPU],
                           self.alloc[:, CPU])
               + self._least(self.requested[:, MEMORY] + nz[MEMORY],
                             self.alloc[:, MEMORY])) // 2
        load = self.usage + self.assigned_est
        la = (self._least(load[:, CPU] + est[CPU], self.alloc[:, CPU])
              + self._least(load[:, MEMORY] + est[MEMORY],
                            self.alloc[:, MEMORY])) // 2
        la = np.where(self.fresh, la, 0)
        return fit + la

    def feasible(self, req, quota):
        ok = self.loadaware_ok.copy()
        for r in np.flatnonzero(req > 0):
            ok &= self.requested[:, r] + req[r] <= self.alloc[:, r]
        if quota >= 0 and self.runtime is not None:
            lim = self.limited[quota] != 0
            if np.any(self.used[quota][lim] + req[lim]
                      > self.runtime[quota][lim]):
                ok[:] = False
        return ok

    def place(self, node, req, est, quota, sign=1):
        """Commit a placement (``sign`` -1 releases it)."""
        self.requested[node] += sign * req
        self.assigned_est[node] += sign * est
        if quota >= 0 and self.runtime is not None:
            self.used[quota] += sign * req


def assign(state, scoring) -> np.ndarray:
    """Bound answers of every pod row of ``state``: node index or -1."""
    return run(state, scoring)[0]


def run(state, scoring):
    """The whole cycle over every pod row of ``state``.  Returns the
    bound answers, the number of gangs undone partway (a member placed,
    then one that fit nowhere), and the ``Cycle`` as it ends."""
    cyc = Cycle(state, scoring)
    req = np.asarray(state["pod_requests"], np.int64)
    est = np.asarray(state["pod_estimated"], np.int64)
    prio = np.asarray(state["priority"], np.int64)
    quota = np.asarray(state["quota_id"], np.int64)
    out = np.full(len(req), -1, np.int64)
    gang = np.asarray(state.get("gang_id", np.full(len(req), -1)), np.int64)
    placed = {}  # gang -> its rows placed in this cycle
    failed = set()  # gangs whose schedule cycle is invalid
    partway = 0
    for i in np.lexsort((np.arange(len(req)), -prio)):
        g = int(gang[i])
        if g in failed:
            continue
        ok = cyc.feasible(req[i], quota[i])
        if not ok.any():
            if g >= 0:
                rows = placed.pop(g, [])
                for r in rows:  # PostFilter rejects, Unreserve releases
                    cyc.place(out[r], req[r], est[r], quota[r], sign=-1)
                    out[r] = -1
                failed.add(g)
                partway += bool(rows)
            continue
        node = int(np.argmax(np.where(ok, cyc.scores(req[i], est[i]), -1)))
        out[i] = node
        cyc.place(node, req[i], est[i], quota[i])
        if g >= 0:
            placed.setdefault(g, []).append(i)
    return out, partway, cyc


def score_list(state, scoring, pod: int = 0):
    """One pod's feasible nodes and scores, best first: (node_index,
    score) as int32 and int64 arrays."""
    cyc = Cycle(state, scoring)
    req = np.asarray(state["pod_requests"], np.int64)[pod]
    est = np.asarray(state["pod_estimated"], np.int64)[pod]
    quota = int(np.asarray(state["quota_id"])[pod])
    ok = cyc.feasible(req, quota)
    nodes = np.flatnonzero(ok)
    score = cyc.scores(req, est)[nodes]
    order = np.lexsort((nodes, -score))
    return nodes[order].astype(np.int32), score[order].astype(np.int64)
