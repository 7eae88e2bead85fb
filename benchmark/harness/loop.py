"""The one general traffic generator: a closed scheduling loop.

A traffic mix is a data file (``benchmark/traffic/<name>.json``); this
module reads its parameters and drives one ``ScorerClient`` through the
served path.  Each cycle is one delta Sync followed by one request:

* ``"rpc": "assign"`` — an Assign over a pending backlog of
  ``backlog_pods`` rows.  The answer kept is the bound answer: the node
  of every pod whose status is ASSIGNED, else -1.  The binder commits
  every bound pod into node ``requested`` and the quota's used; as many
  bound pods depart, oldest first (``"departures": "as_placed"``), so
  the fill stays where set-up left it; new pods drawn from the
  configuration arrive in the rows of the pods bound.  Where the
  configuration declares gangs (``cluster.validate_gangs``), every row
  is a gang member: the binder binds a gang only when all its members
  are ASSIGNED (Permit lets the group through together), and one
  freshly drawn gang takes the rows of each gang bound.
* ``"rpc": "score_flat"`` — ``ScoreFlat(top_k)`` for a one-pod table,
  the Go plugin's PreScore.  The binder puts the pod on its
  highest-scored node (the lowest index on ties); the next pod arrives.

Every cycle, ``usage rows per cycle`` nodes report new usage
(koordlet's NodeMetric): nodes / report interval * ``cycle_s`` rows,
fixed per cycle so that every seed does the same work.  All draws come
from the seed; the answers only decide where pods are bound.
"""

from __future__ import annotations

import contextlib
import hashlib
import time

import numpy as np

from . import cluster as C

# the wire's status of a pod that may bind (solver STATUS_ASSIGNED)
ASSIGNED = 0

# the arrays a Sync carries as delta-encoded tensors whose changed cells
# the server scatters on the device
TENSORS = ("node_requested", "node_usage", "pod_requests", "pod_estimated",
           "quota_runtime", "quota_used", "quota_limited")


def scatter_bucket(changed: int, size: int):
    """The device update a Sync that changes ``changed`` of a tensor's
    ``size`` cells runs: None for none, "full" past a quarter of the
    cells (bridge/state.py numpy_to_tensor), else the scatter program of
    the power-of-two bucket, at least 8 (model/snapshot.py pad_bucket)."""
    if changed <= 0:
        return None
    if changed > max(1, int(size * 0.25)):
        return "full"
    b = 8
    while b < changed:
        b *= 2
    return b


def buckets_between(lo: int, hi: int, size: int):
    """Every device update that a change of ``lo`` to ``hi`` cells runs."""
    out = {scatter_bucket(lo, size), scatter_bucket(hi, size)}
    b = 8
    while b < hi:
        if b >= lo:
            out.add(scatter_bucket(b, size))
        b *= 2
    out.discard(None)
    return out


def _annotate(on):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def digest(node_index, score) -> bytes:
    """A score list's fingerprint: node indices then scores."""
    h = hashlib.blake2b(digest_size=16)
    h.update(np.ascontiguousarray(node_index, "<i4").tobytes())
    h.update(np.ascontiguousarray(score, "<i8").tobytes())
    return h.digest()


class Loop:
    """The cluster, its backlog and the loop's state for one run."""

    def __init__(self, config, traffic, seed: int):
        self.config = config
        self.traffic = traffic
        self.rpc = traffic["rpc"]
        rs = np.random.RandomState(seed % 2**32)
        self.nodes = C.make_nodes(rs, config["nodes"])
        n = len(self.nodes.names)
        backlog = int(traffic["backlog_pods"])
        # rows a gang (the block is checked at load); 1 where every row
        # is a pod of its own
        self.gangs = config.get("gangs")
        m = self.members = self.gangs["members"] if self.gangs else 1
        self.pods = C.make_pods(rs, config["pods"], backlog, "pod", m)
        self.gang_id = (np.arange(backlog) // m if self.gangs
                        else np.full(backlog, -1, np.int64))
        self.quotas = None
        if config.get("quota"):
            self.quotas = C.make_quotas(rs, config["quota"], self.nodes.alloc)
            # a gang's members are of one tenant
            self.pods.quota = (np.arange(backlog) // m
                               % config["quota"]["tenants"])
        # the traffic's own stream: arrivals, departures, usage reports
        self.gen = np.random.default_rng([seed % 2**63, 1])
        self.serial = backlog
        self.est_mode = traffic["estimates"]
        self.pod_est = self._estimates(self.pods)
        self.usage_rows = max(1, round(
            n * float(traffic["cycle_s"])
            / float(config["usage_report_interval_s"])))
        # bound pods, oldest first: node, request row, quota
        self.bound_node = np.zeros(0, np.int64)
        self.bound_req = np.zeros((0, C.R), np.int64)
        self.bound_quota = np.zeros(0, np.int64)
        init = config.get("init_pods")
        if init:
            first = C.draw_pods(self.gen, config["pods"], int(init), "init", 0)
            spots = np.arange(int(init)) * n // int(init)
            self._bind(spots, first.requests, first.quota)
        self.answer = None  # the last request's answer, for the binder
        self.synced_once = False
        self.sent = None  # the last Sync's keywords
        self.changed = {}  # per tensor: the cells each Sync changed

    # -- state ------------------------------------------------------------

    def _estimates(self, pods):
        if self.est_mode == "requests":
            return pods.requests.copy()
        return C.estimate(pods.requests, pods.limits, pods.pclass,
                          self.config["scoring"])

    def _add_requested(self, nodes, req, sign):
        n = len(self.nodes.names)
        for r in np.flatnonzero(req.any(axis=0)):
            col = np.bincount(nodes, weights=req[:, r], minlength=n)
            self.nodes.requested[:, r] += sign * col.astype(np.int64)

    def _bind(self, nodes, req, quota):
        self._add_requested(nodes, req, 1)
        self.bound_node = np.concatenate([self.bound_node, nodes])
        self.bound_req = np.concatenate([self.bound_req, req])
        self.bound_quota = np.concatenate([self.bound_quota, quota])

    def _depart(self, count):
        if count <= 0:
            return
        self._add_requested(self.bound_node[:count],
                            self.bound_req[:count], -1)
        self.bound_node = self.bound_node[count:]
        self.bound_req = self.bound_req[count:]
        self.bound_quota = self.bound_quota[count:]

    def _arrive(self, rows):
        """New pods from the configuration's distribution in ``rows``."""
        if not len(rows):
            return
        new = C.draw_pods(self.gen, self.config["pods"], len(rows), "pod",
                          self.serial, self.members)
        self.serial += len(rows)
        p = self.pods
        p.requests[rows] = new.requests
        p.limits[rows] = new.limits
        p.priority[rows] = new.priority
        p.pclass[rows] = new.pclass
        for i, r in enumerate(rows):
            p.names[r] = new.names[i]
        self.pod_est[rows] = self._estimates(new)

    def _report_usage(self):
        """koordlet usage reports: the trace generator's drift recipe,
        halfway from the old usage to a fresh fraction of allocatable."""
        nd = self.nodes
        rows = self.gen.choice(len(nd.names), self.usage_rows, replace=False)
        self.last_usage_rows = rows
        lo, hi = self.config["usage_drift_frac"]
        frac = self.gen.uniform(lo, hi, len(rows))
        for r in (C.CPU, C.MEMORY):
            nd.usage[rows, r] = (nd.usage[rows, r] * 0.5
                                 + nd.alloc[rows, r] * frac * 0.5
                                 ).astype(np.int64)

    def _quota_tables(self):
        q = self.quotas
        t = q.min.shape[0]
        used = np.zeros((t, C.R), np.int64)
        pend = np.zeros((t, C.R), np.int64)
        np.add.at(used, self.bound_quota[self.bound_quota >= 0],
                  self.bound_req[self.bound_quota >= 0])
        np.add.at(pend, self.pods.quota, self.pods.requests)
        runtime, limited = C.quota_runtime(q, used + pend,
                                           self.nodes.alloc.sum(axis=0))
        return runtime, used, limited

    def advance(self):
        """Between two requests: bind the last answer, let pods depart
        and arrive, take the usage reports."""
        if self.answer is not None:
            if self.rpc == "assign":
                m = self.members
                whole = (self.answer >= 0).reshape(-1, m).all(axis=1)
                rows = np.flatnonzero(np.repeat(whole, m))
                self._bind(self.answer[rows], self.pods.requests[rows],
                           self.pods.quota[rows])
                if self.traffic["departures"] == "as_placed":
                    self._depart(len(rows))
                self._arrive(rows)
            else:
                if self.answer >= 0:
                    self._bind(np.array([self.answer]),
                               self.pods.requests[:1], self.pods.quota[:1])
                self._arrive(np.arange(len(self.pods)))
        self._report_usage()

    def state(self):
        """The logical state the next Sync carries, as the reference
        reads it (arrays are the loop's own; copy to keep)."""
        s = {
            "node_allocatable": self.nodes.alloc,
            "node_requested": self.nodes.requested,
            "node_usage": self.nodes.usage,
            "metric_fresh": np.ones(len(self.nodes.names), bool),
            "pod_requests": self.pods.requests,
            "pod_estimated": self.pod_est,
            "priority": self.pods.priority,
            "quota_id": self.pods.quota,
            "gang_id": self.gang_id,
        }
        if self.quotas is not None:
            s["quota_runtime"], s["quota_used"], s["quota_limited"] = (
                self._quota_tables())
        return s

    def sync_kwargs(self, state):
        """ScorerClient.sync keywords.  Node names and freshness ride the
        first Sync only (the Go plugin omits them on deltas), and so
        does the gang table, which is constant for the run; pod names,
        priorities and gang ids ride every Sync, since pods change."""
        # copies: the client keeps the arrays it sent as its delta
        # baseline, and this loop updates its own in place
        kw = dict(
            node_allocatable=state["node_allocatable"].copy(),
            node_requested=state["node_requested"].copy(),
            node_usage=state["node_usage"].copy(),
            pod_requests=state["pod_requests"].copy(),
            pod_estimated=state["pod_estimated"].copy(),
            pod_names=list(self.pods.names),
            priority=state["priority"].tolist(),
            gang_id=state["gang_id"].tolist(),
            quota_id=state["quota_id"].tolist(),
        )
        if not self.synced_once:
            kw["node_names"] = list(self.nodes.names)
            kw["metric_fresh"] = state["metric_fresh"].tolist()
            if self.gangs:
                kw["gang_min_member"] = (
                    [self.members] * (len(self.pods) // self.members))
        if self.quotas is not None:
            kw["quota_runtime"] = state["quota_runtime"]
            kw["quota_used"] = state["quota_used"]
            kw["quota_limited"] = state["quota_limited"]
        return kw

    def _note_changes(self, kw):
        if self.sent is not None:
            for k in TENSORS:
                if k in kw and k in self.sent:
                    self.changed.setdefault(k, []).append(
                        int(np.count_nonzero(kw[k] != self.sent[k])))
        self.sent = kw

    def warm_buckets(self, client):
        """Set-up only, after the warm-up's cycles: the delta Sync's
        device updates come in buckets of the changed cells, and the
        count of them varies from cycle to cycle.  For each tensor, every
        bucket within the range of counts the cycles met, widened by that
        range on each side, that no cycle met is run once: a Sync that
        changes that many cells, then the Sync that undoes it.  Returns
        the (tensor, bucket) pairs so run."""
        warmed = []
        for k, counts in sorted(self.changed.items()):
            size = self.sent[k].size
            lo, hi = min(counts), max(counts)
            if hi == 0:
                continue  # a tensor the traffic never changes
            seen = {scatter_bucket(c, size) for c in counts}
            want = buckets_between(max(1, 2 * lo - hi), 2 * hi - lo, size)
            for b in sorted(want - seen, key=str):
                cap = max(1, int(size * 0.25))
                c = cap + 1 if b == "full" else min(b, cap)
                arr = np.array(self.sent[k], copy=True)
                arr.reshape(-1)[:c] += 1
                client.sync(**dict(self.sent, **{k: arr}))
                client.sync(**self.sent)
                warmed.append((k, b))
        return warmed

    # -- one cycle --------------------------------------------------------

    def cycle(self, client, annotate=None, keep=None):
        """Advance, Sync, request.  Returns the cycle's record; ``keep``
        (optional) receives the state the Sync carried, before it is
        sent."""
        note = annotate or (lambda name: contextlib.nullcontext())
        tb, cpu = time.perf_counter(), time.process_time()
        with note("bench.binder"):
            self.advance()
            state = self.state()
            if keep is not None:
                keep(state)
            kw = self.sync_kwargs(state)
            self._note_changes(kw)
        t0 = time.perf_counter()
        with note("bench.sync"):
            client.sync(**kw)
        t1 = time.perf_counter()
        self.synced_once = True
        rec = {"binder_ms": (t0 - tb) * 1e3, "sync_ms": (t1 - t0) * 1e3}
        if self.rpc == "assign":
            with note("bench.assign"):
                assignment, status, cycle_ms, path = client.assign()
            t2 = time.perf_counter()
            p = len(self.pods)
            self.answer = np.where(np.asarray(status[:p]) == ASSIGNED,
                                   np.asarray(assignment[:p], np.int64), -1)
            rec.update(rpc_ms=(t2 - t1) * 1e3, server_ms=float(cycle_ms),
                       path=path, placed=int((self.answer >= 0).sum()))
        else:
            with note("bench.score"):
                pod_index, counts, node_index, score = client.score_flat(
                    int(self.traffic["top_k"]))
            t2 = time.perf_counter()
            # group 0 is the one pod of the table
            c = int(counts[0]) if len(pod_index) and pod_index[0] == 0 else 0
            ni, sc = node_index[:c], score[:c]
            if c:
                best = sc.max()
                self.answer = int(ni[sc == best].min())
            else:
                self.answer = -1
            rec.update(rpc_ms=(t2 - t1) * 1e3, placed=int(c > 0),
                       digest=digest(ni, sc))
        rec["cycle_ms"] = (t2 - t0) * 1e3
        rec["start"] = tb
        rec["cpu_ms"] = (time.process_time() - cpu) * 1e3
        return rec


def copy_state(state):
    return {k: np.array(v, copy=True) for k, v in state.items()}
