"""Generate the golden wire fixtures for go/scorerclient/golden_test.go.

Runs the REAL Python servicer (bridge/server.py) on a small
quota+gang snapshot and records, for each RPC of the raw-UDS seam
(bridge/udsserver.py framing):

* the request bytes the Python protobuf runtime produces (the Go
  marshaler must match them byte-for-byte),
* the reply bytes the servicer produces (the Go unmarshaler must decode
  them to the values in expected.json).

Usage (from the repo root, CPU backend is fine):

    JAX_PLATFORMS=cpu python go/gen_fixtures.py

Outputs are committed under go/scorerclient/testdata/ so the Go test
runs in CI with no Python present.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# fixtures are generated on the CPU even where a TPU is visible: the
# config update wins over the environment (tests/conftest.py does the same)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import koordinator_tpu  # noqa: F401,E402
from koordinator_tpu.bridge.codegen import pb2  # noqa: E402
from koordinator_tpu.bridge.server import ScorerServicer  # noqa: E402


def _golden_servicer(epoch: str) -> ScorerServicer:
    """A servicer with a PINNED boot epoch: the epoch rides every
    snapshot id ("s<epoch>-<gen>") into the fixtures, and a random
    uuid there would rewrite every .bin + expected.json on each regen
    — unreviewable binary churn for zero semantic change."""
    sv = ScorerServicer()
    sv._epoch = epoch
    sv.telemetry.spans.epoch = epoch  # minted cycle ids stay aligned
    return sv
from koordinator_tpu.harness import generators  # noqa: E402
from koordinator_tpu.harness.golden import build_sync_request  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scorerclient", "testdata")
TOP_K = 4


def tensor_json(t: "pb2.Tensor") -> dict:
    return {
        "shape": list(t.shape),
        "data": np.frombuffer(t.data, "<i8").tolist(),
    }


def plugin_flow_fixtures(blobs: dict, expected: dict) -> None:
    """Fixtures for the Go plugin's warm-cycle delta sync
    (go/plugin/batchedtpuscorer.go buildSync + scorerclient DeltaTensor):
    a full single-pod sync, then a delta sync against it, then a flat
    Score — generated through bridge/plugin_sim.py (the executable spec)
    and replayed through the REAL servicer.  golden_test.go rebuilds the
    requests with DeltaTensor and must match byte-for-byte."""
    from koordinator_tpu.bridge.plugin_sim import (
        NUM_AXES,
        ResidentMirror,
        build_sync,
        node_vectors,
    )

    def vec(cpu=0, mem=0, pods=0):
        v = [0] * NUM_AXES
        v[0], v[1], v[3] = cpu, mem, pods
        return v

    alloc = vec(cpu=8000, mem=16384, pods=110)
    req1 = vec(cpu=1000, mem=1024, pods=5)
    nodes1 = [(f"plugin-node-{i}", alloc, req1) for i in range(4)]
    metrics = {"plugin-node-0": vec(cpu=500, mem=512)}
    pod_vec = vec(cpu=500, mem=512, pods=1)

    names, a1, r1, u1, f1 = node_vectors(nodes1, metrics)
    mirror = ResidentMirror()
    sync1 = build_sync(
        mirror, False, names, a1, r1, u1, f1, "plugin-pod-1", pod_vec, 0
    )

    sv = _golden_servicer("f1edf1ed")
    reply1 = sv.sync(pb2.SyncRequest.FromString(sync1))
    mirror.names, mirror.alloc, mirror.requested, mirror.usage = (
        names, a1, r1, u1,
    )
    mirror.gen, mirror.valid = 1, True

    # warm cycle: one node's committed load moves
    nodes2 = list(nodes1)
    nodes2[2] = ("plugin-node-2", alloc, vec(cpu=1500, mem=1536, pods=6))
    names2, a2, r2, u2, f2 = node_vectors(nodes2, metrics)
    sync2 = build_sync(
        mirror, True, names2, a2, r2, u2, f2, "plugin-pod-2", pod_vec, 0
    )
    reply2 = sv.sync(pb2.SyncRequest.FromString(sync2))
    score_req = pb2.ScoreRequest(
        snapshot_id=reply2.snapshot_id, top_k=0, flat=True
    )
    score_reply = sv.score(score_req)
    score_reply.build_ms = 0.125  # measured timing pinned: regen determinism

    # both encoders must agree byte-for-byte before the bytes become truth
    for raw in (sync1, sync2):
        assert pb2.SyncRequest.FromString(raw).SerializeToString() == raw

    blobs.update(
        {
            "plugin_sync1_request.bin": sync1,
            "plugin_sync1_reply.bin": reply1.SerializeToString(),
            "plugin_sync2_request.bin": sync2,
            "plugin_sync2_reply.bin": reply2.SerializeToString(),
            "plugin_score2_request.bin": score_req.SerializeToString(),
            "plugin_score2_reply.bin": score_reply.SerializeToString(),
        }
    )
    expected["plugin_flow"] = {
        "names": names,
        "alloc1": a1, "req1": r1, "usage1": u1, "fresh1": f1,
        "alloc2": a2, "req2": r2, "usage2": u2, "fresh2": f2,
        "pod1": "plugin-pod-1", "pod2": "plugin-pod-2",
        "pod_vec": pod_vec,
        "sync1_reply": {
            "snapshot_id": reply1.snapshot_id,
            "nodes": reply1.nodes, "pods": reply1.pods,
        },
        "sync2_reply": {
            "snapshot_id": reply2.snapshot_id,
            "nodes": reply2.nodes, "pods": reply2.pods,
        },
        "score2_reply": {
            "pod_index": np.frombuffer(score_reply.flat.pod_index, "<i4").tolist(),
            "counts": np.frombuffer(score_reply.flat.counts, "<i4").tolist(),
            "node_index": np.frombuffer(score_reply.flat.node_index, "<i4").tolist(),
            "score": np.frombuffer(score_reply.flat.score, "<i8").tolist(),
        },
    }

    # the round-4 advisory regression: empty repeated-string elements
    # must survive (dropping one misaligns names with tensor rows)
    empty = pb2.SyncRequest()
    empty.pods.names.extend(["", "pod-b"])
    blobs["empty_name_request.bin"] = empty.SerializeToString()
    expected["empty_name"] = {"pod_names": ["", "pod-b"]}


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    nodes, pods, _, quotas = generators.quota_colocation(pods=32, nodes=8)
    gangs = [{"name": "gang-0", "min_member": 2}]
    pods[0]["gang"] = "gang-0"
    pods[1]["gang"] = "gang-0"
    req, _ = build_sync_request(
        nodes, pods, gangs, quotas, node_bucket=8, pod_bucket=32
    )

    sv = _golden_servicer("0601den0")
    # trace context rides every request fixture (ISSUE 14) so the Go
    # marshaler's trace_id/parent_span fields are byte-pinned, and the
    # replies carry the servicer's DETERMINISTIC span ids (counter-
    # based under the pinned epoch: sp0601den0-<n>) for the unmarshal
    # tests.  Pinned values, never minted — regen determinism.
    req.trace_id = "ab" * 16
    req.parent_span = "1111222233334444"
    sync_reply = sv.sync(req)
    # deadline budget + band ride the request fixtures (ISSUE 13) so
    # the Go marshaler's new fields are byte-pinned like every other
    score_req = pb2.ScoreRequest(
        snapshot_id=sync_reply.snapshot_id, top_k=TOP_K, flat=True,
        deadline_ms=1500, band="koord-batch",
        trace_id="cd" * 16, parent_span="5555666677778888",
    )
    score_reply = sv.score(score_req)
    assign_req = pb2.AssignRequest(
        snapshot_id=sync_reply.snapshot_id, cycle_id="golden-cycle-1",
        deadline_ms=2500, band="koord-prod",
        trace_id="ef" * 16, parent_span="9999aaaabbbbcccc",
    )
    assign_reply = sv.assign(assign_req)
    # measured timings pinned to exact float64 constants: a fixture
    # regen with zero semantic change must be byte-identical
    score_reply.build_ms = 0.125
    assign_reply.cycle_ms = 1.5

    blobs = {
        "sync_request.bin": req.SerializeToString(),
        "sync_reply.bin": sync_reply.SerializeToString(),
        "score_request.bin": score_req.SerializeToString(),
        "score_reply.bin": score_reply.SerializeToString(),
        "assign_request.bin": assign_req.SerializeToString(),
        "assign_reply.bin": assign_reply.SerializeToString(),
    }

    expected = {
        "top_k": TOP_K,
        "score_request": {
            "deadline_ms": score_req.deadline_ms,
            "band": score_req.band,
            "trace_id": score_req.trace_id,
            "parent_span": score_req.parent_span,
        },
        "sync_request": {
            "node_bucket": req.node_bucket,
            "pod_bucket": req.pod_bucket,
            "trace_id": req.trace_id,
            "parent_span": req.parent_span,
            "nodes": {
                "names": list(req.nodes.names),
                "metric_fresh": list(req.nodes.metric_fresh),
                "allocatable": tensor_json(req.nodes.allocatable),
                "requested": tensor_json(req.nodes.requested),
                "usage": tensor_json(req.nodes.usage),
            },
            "pods": {
                "names": list(req.pods.names),
                "requests": tensor_json(req.pods.requests),
                "estimated": tensor_json(req.pods.estimated),
                "priority": list(req.pods.priority),
                "gang_id": list(req.pods.gang_id),
                "quota_id": list(req.pods.quota_id),
                "priority_class": list(req.pods.priority_class),
            },
            "gangs": {"min_member": list(req.gangs.min_member)},
            "quotas": {
                "runtime": tensor_json(req.quotas.runtime),
                "used": tensor_json(req.quotas.used),
                "limited": tensor_json(req.quotas.limited),
            },
        },
        "sync_reply": {
            "snapshot_id": sync_reply.snapshot_id,
            "nodes": sync_reply.nodes,
            "pods": sync_reply.pods,
            "server_span": sync_reply.server_span,
        },
        "score_reply": {
            "pod_index": np.frombuffer(
                score_reply.flat.pod_index, "<i4"
            ).tolist(),
            "counts": np.frombuffer(score_reply.flat.counts, "<i4").tolist(),
            "node_index": np.frombuffer(
                score_reply.flat.node_index, "<i4"
            ).tolist(),
            "score": np.frombuffer(score_reply.flat.score, "<i8").tolist(),
        },
        "assign_request": {
            # the correlation id the sidecar echoes (and stamps on its
            # span/flight telemetry); byte-parity tests re-marshal it
            "cycle_id": assign_req.cycle_id,
            "deadline_ms": assign_req.deadline_ms,
            "band": assign_req.band,
            "trace_id": assign_req.trace_id,
            "parent_span": assign_req.parent_span,
        },
        "assign_reply": {
            "assignment": list(assign_reply.assignment),
            "status": list(assign_reply.status),
            "path": assign_reply.path,
            "cycle_id": assign_reply.cycle_id,
            "server_span": assign_reply.server_span,
        },
        "score_reply_server_span": score_reply.server_span,
    }
    plugin_flow_fixtures(blobs, expected)

    for name, data in blobs.items():
        with open(os.path.join(OUT, name), "wb") as f:
            f.write(data)
    with open(os.path.join(OUT, "expected.json"), "w") as f:
        json.dump(expected, f, indent=1, sort_keys=True)
    print(f"wrote {len(blobs)} fixtures + expected.json to {OUT}")


if __name__ == "__main__":
    main()
