#!/usr/bin/env python3
"""Bring-up smoke: the served Sync -> Score -> Assign path on the chip.

Drives the koord-scheduler daemon (``SchedulerServer``, served over UDS)
the way the Go BatchedTPUScorer plugin does, through bridge/client.py's
``ScorerClient``, at BASELINE.json config 4: the ElasticQuota
multi-tenant LS/BE mix, 10,000 pending pods x 2,000 nodes, generated
from ``--seed``.  Phases, in one process that holds the chip:

1. full Sync of the cluster;
2. cold: Score (top-k), then Assign;
3. warm: a delta Sync (usage moves on a few dozen nodes, a few hundred
   pending pods leave and as many arrive in their rows), Score, Assign;
   a second delta Sync, Assign.

Checks, any failure exits non-zero without the result line:

* every Assign ran the dense Pallas kernel (``path == "pallas"``);
* every Assign equals, pod for pod, the independent sequential C++
  cycle (native/score_baseline.cpp, built by ``make`` from the sources);
* Score top-k for a seeded sample of pods equals the
  harness/reference.py oracle;
* pods were placed, and the last warm Assign compiled nothing.

Each phase line prints its wall time, XLA compiles and their seconds,
persistent-cache hits and device memory: information, not metrics.
The last line is one JSON object naming the device.

``--chips 4`` runs only the mesh phase: ``SchedulerServer(mesh_devices=
"4")`` takes the same full Sync, Assign, one warm delta, Assign — each
``path == "shard"`` and equal to the C++ cycle — and prints the device
holding each node shard.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(ROOT, ".smoke")  # socket, lease, state, goldens

PODS, NODES = 10_000, 2_000  # BASELINE.json config 4
TOP_K = 8
SCORE_SAMPLE = 24


class CompileWatch:
    """XLA compiles (count, seconds), jaxpr traces and persistent-cache
    hits, read off jax's monitoring bus."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.traces, self.cache_hits)


class Smoke:
    def __init__(self):
        import jax

        self.watch = CompileWatch()
        self.device = jax.devices()[0]
        self.failures = []
        self.t0 = time.perf_counter()

    def phase(self, name, fn):
        """Run one phase; print its wall time, compile counters and
        device memory.  Returns (result, compiles in the phase)."""
        before = self.watch.mark()
        t0 = time.perf_counter()
        out = fn()
        wall_ms = (time.perf_counter() - t0) * 1e3
        after = self.watch.mark()
        compiles = after[0] - before[0]
        mem = self.device.memory_stats() or {}
        print(
            f"phase={name} at_s={time.perf_counter() - self.t0:.1f} "
            f"wall_ms={wall_ms:.1f} compiles={compiles} "
            f"compile_s={after[1] - before[1]:.2f} "
            f"traces={after[2] - before[2]} "
            f"cache_hits={after[3] - before[3]} "
            f"hbm_in_use={mem.get('bytes_in_use')} "
            f"hbm_peak={mem.get('peak_bytes_in_use')}",
            flush=True,
        )
        return out, compiles

    def check(self, ok, what):
        print(f"check {'ok' if ok else 'FAILED'}: {what}", flush=True)
        if not ok:
            self.failures.append(what)


# -- the cluster and its churn -------------------------------------------

def churn(rng, nodes, pods, step, usage_nodes, churn_pods):
    """One warm delta of the logical cluster, in place: usage moves on
    ``usage_nodes`` nodes; ``churn_pods`` pending pods leave and as many
    new ones arrive in their rows.  Row counts stay fixed, so the Sync
    lands as sparse deltas on the resident device tensors."""
    from koordinator_tpu.harness import generators

    for i in rng.choice(len(nodes), usage_nodes, replace=False):
        alloc = nodes[i]["allocatable"]
        cpu = int(alloc["cpu"][:-1])
        nodes[i]["usage"] = {
            "cpu": f"{int(cpu * rng.uniform(0.05, 0.75))}m",
            "memory": int(alloc["memory"] * rng.uniform(0.05, 0.75)),
        }
    arrivals = generators._random_pods(
        rng, churn_pods, name_prefix=f"delta{step}-pod"
    )
    for i, pod in zip(rng.choice(len(pods), churn_pods, replace=False),
                      arrivals):
        pod["quota"] = pods[i]["quota"]
        pods[i] = pod


def sync_kwargs(req):
    """The golden SyncRequest as ScorerClient.sync keywords; the client
    delta-encodes them against its acked baseline."""
    from koordinator_tpu.bridge.state import tensor_to_numpy

    def t(msg):
        return tensor_to_numpy(msg, None)

    return dict(
        node_allocatable=t(req.nodes.allocatable),
        node_requested=t(req.nodes.requested),
        node_usage=t(req.nodes.usage),
        node_names=list(req.nodes.names),
        metric_fresh=list(req.nodes.metric_fresh),
        pod_requests=t(req.pods.requests),
        pod_estimated=t(req.pods.estimated),
        pod_names=list(req.pods.names),
        priority=list(req.pods.priority),
        gang_id=list(req.pods.gang_id),
        quota_id=list(req.pods.quota_id),
        gang_min_member=list(req.gangs.min_member),
        quota_runtime=t(req.quotas.runtime),
        quota_used=t(req.quotas.used),
        quota_limited=t(req.quotas.limited),
    )


# -- the two independent answers -----------------------------------------

def native_binary():
    """The sequential C++ cycle, built from the committed sources
    (make is a no-op when it is up to date)."""
    native = os.path.join(ROOT, "native")
    subprocess.run(["make", "-C", native, "score_baseline"], check=True,
                   capture_output=True, timeout=300)
    return os.path.join(native, "score_baseline")


def native_assign(binary, workdir, req, tag):
    golden = os.path.join(workdir, f"golden-{tag}.bin")
    with open(golden, "wb") as fh:
        fh.write(req.SerializeToString())
    out = subprocess.run([binary, golden, "1", "1"], check=True,
                         capture_output=True, text=True, timeout=600)
    return [int(v) for v in out.stdout.splitlines()[1].split()[1:]]


def oracle_top_k(req, sample, k):
    """harness/reference.py's Filter+Score for each sampled pod against
    the synced state: feasible nodes by score descending, lower node
    index first on ties."""
    from koordinator_tpu.bridge.state import tensor_to_numpy
    from koordinator_tpu.harness.reference import ReferenceCycle
    from koordinator_tpu.model.snapshot import PriorityClass

    def t(msg):
        return tensor_to_numpy(msg, None).tolist()

    cyc = ReferenceCycle(
        t(req.nodes.allocatable), t(req.nodes.requested),
        t(req.nodes.usage), list(req.nodes.metric_fresh),
    )
    preq, pest = t(req.pods.requests), t(req.pods.estimated)
    out = {}
    for i in sample:
        prod = req.pods.priority_class[i] == int(PriorityClass.PROD)
        ranked = sorted(
            (-cyc.combined_score(n, preq[i], pest[i], prod), n)
            for n in range(len(cyc.alloc))
            if cyc.fit_ok(n, preq[i]) and cyc.loadaware_filter_ok(n, prod)
        )
        out[i] = [(n, -s) for s, n in ranked[:k]]
    return out


# -- phases ---------------------------------------------------------------

def start_server(workdir, mesh_devices=None):
    from koordinator_tpu.bridge.client import ScorerClient
    from koordinator_tpu.scheduler.server import SchedulerServer

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    sock = os.path.join(workdir, "scorer.sock")
    server = SchedulerServer(
        lease_path=os.path.join(workdir, "leader.lease"),
        uds_path=sock,
        state_dir=os.path.join(workdir, "state"),
        mesh_devices=mesh_devices,
    ).start()
    deadline = time.monotonic() + 30
    while not server.elector.is_leader and time.monotonic() < deadline:
        time.sleep(0.05)
    if not server.elector.is_leader:
        server.stop()
        raise RuntimeError("the scheduler never took its lease")
    return server, ScorerClient(f"unix://{sock}")


def run_assign(smoke, client, binary, workdir, req, tag, want_path):
    (assignment, _status, cycle_ms, path), compiles = smoke.phase(
        f"{tag}_assign", client.assign
    )
    want = native_assign(binary, workdir, req, tag)
    placed = int((assignment >= 0).sum())
    print(f"{tag}_assign path={path} placed={placed}/{len(assignment)} "
          f"server_cycle_ms={cycle_ms:.1f}", flush=True)
    smoke.check(path == want_path, f"{tag} Assign path {path!r} == "
                f"{want_path!r}")
    diff = sum(int(a) != b for a, b in zip(assignment, want))
    smoke.check(len(assignment) == len(want) and diff == 0,
                f"{tag} Assign equals the C++ cycle on all "
                f"{len(want)} pods ({diff} differ)")
    smoke.check(placed > 0, f"{tag} Assign placed {placed} pods")
    return compiles


def run_score(smoke, client, req, rng, tag):
    (pod_index, counts, node_index, score), _ = smoke.phase(
        f"{tag}_score", lambda: client.score_flat(TOP_K)
    )
    starts = {int(p): (int(o), int(c)) for p, o, c in zip(
        pod_index, counts.cumsum() - counts, counts)}
    sample = sorted(rng.choice(len(req.pods.names), SCORE_SAMPLE,
                               replace=False).tolist())
    want = oracle_top_k(req, sample, TOP_K)
    bad = []
    for i in sample:
        o, c = starts.get(i, (0, 0))
        got = list(zip(node_index[o:o + c].tolist(),
                       score[o:o + c].tolist()))
        if got != want[i]:
            bad.append(i)
    smoke.check(not bad, f"{tag} Score top-{TOP_K} equals the reference "
                f"oracle on {len(sample)} sampled pods (differ: {bad})")


def run_single(seed=0, pods=PODS, nodes=NODES, workdir=WORKDIR):
    """The one-chip phases.  Returns the list of failed checks."""
    import numpy as np

    from koordinator_tpu.harness import generators
    from koordinator_tpu.harness.golden import build_sync_request

    smoke = Smoke()
    rng = np.random.RandomState(seed)
    binary = native_binary()
    cluster = list(generators.quota_colocation(
        seed=seed, pods=pods, nodes=nodes
    ))
    usage_nodes, churn_pods = min(48, nodes // 4), min(300, pods // 8)
    server, client = start_server(workdir)
    try:
        req, _ = build_sync_request(*cluster)
        smoke.phase("full_sync", lambda: client.sync(**sync_kwargs(req)))
        run_score(smoke, client, req, rng, "cold")
        run_assign(smoke, client, binary, workdir, req, "cold", "pallas")
        for step in (1, 2):
            churn(rng, cluster[0], cluster[1], step, usage_nodes,
                  churn_pods)
            req, _ = build_sync_request(*cluster)
            smoke.phase(f"warm{step}_sync",
                        lambda: client.sync(**sync_kwargs(req)))
            smoke.check(server.servicer.state.last_sync_path == "warm",
                        f"delta Sync {step} landed on the resident "
                        "tensors")
            if step == 1:
                run_score(smoke, client, req, rng, "warm1")
            compiles = run_assign(smoke, client, binary, workdir, req,
                                  f"warm{step}", "pallas")
        smoke.check(compiles == 0,
                    f"last warm Assign compiled nothing ({compiles})")
        print(f"total at_s={time.perf_counter() - smoke.t0:.1f} "
              f"compiles={smoke.watch.compiles} "
              f"compile_s={smoke.watch.compile_s:.2f} "
              f"cache_hits={smoke.watch.cache_hits}", flush=True)
    finally:
        client.close()
        server.stop()
    return smoke.failures


def run_mesh(chips, seed=0, pods=PODS, nodes=NODES, workdir=WORKDIR):
    """The multi-chip phase: the mesh-resident daemon's shard Assign.
    Returns the list of failed checks."""
    import numpy as np

    from koordinator_tpu.harness import generators
    from koordinator_tpu.harness.golden import build_sync_request

    smoke = Smoke()
    rng = np.random.RandomState(seed)
    binary = native_binary()
    cluster = list(generators.quota_colocation(
        seed=seed, pods=pods, nodes=nodes
    ))
    server, client = start_server(workdir, mesh_devices=str(chips))
    try:
        smoke.check(server.servicer.mesh.size == chips,
                    f"mesh holds {server.servicer.mesh.size} devices")
        req, _ = build_sync_request(*cluster)
        smoke.phase("full_sync", lambda: client.sync(**sync_kwargs(req)))
        run_assign(smoke, client, binary, workdir, req, "cold", "shard")
        churn(rng, cluster[0], cluster[1], 1, min(48, nodes // 4),
              min(300, pods // 8))
        req, _ = build_sync_request(*cluster)
        smoke.phase("warm1_sync", lambda: client.sync(**sync_kwargs(req)))
        smoke.check(server.servicer.state.last_sync_path == "warm",
                    "delta Sync landed on the resident shards")
        run_assign(smoke, client, binary, workdir, req, "warm1", "shard")
        nodes_t = server.servicer.state.snapshot().nodes
        holders = set()
        for name in ("allocatable", "requested", "usage"):
            arr = getattr(nodes_t, name)
            for shard in arr.addressable_shards:
                holders.add(shard.device)
                print(f"shard nodes.{name}{list(shard.index)[:1]} "
                      f"rows={shard.data.shape[0]} on {shard.device}",
                      flush=True)
        smoke.check(len(holders) == chips,
                    f"node shards live on {len(holders)} devices")
    finally:
        client.close()
        server.stop()
    return smoke.failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU: this smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    if args.chips == 1:
        failures = run_single(seed=args.seed)
    else:
        failures = run_mesh(args.chips, seed=args.seed)
    if failures:
        print(f"FAILED: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
