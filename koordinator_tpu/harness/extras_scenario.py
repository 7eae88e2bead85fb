"""Extras-path scenario + native interchange for the C++ baseline.

Round-4 review #4/#6: the composed extended-plugin cycle (NUMA zones,
DeviceShare, Reservation) was parity-checked only against the same-author
Python oracle.  This module gives the extras path an INDEPENDENT check:

* ``extras_scenario`` builds one deterministic cluster whose extras
  tensors exercise all three plugins (zones with a NUMA policy mix,
  GPU/RDMA minors, matched reservations);
* ``plugin_extra_tensors`` composes the real TensorPlugins through the
  FrameworkExtender (exactly what ``--config extras`` feeds the kernel);
* ``write_extras_file`` serializes the RAW subsystem tables (not the
  composed tensors!) into a simple sectioned binary that
  ``native/score_baseline.cpp`` re-derives the mask/scores from — an
  independently-written implementation of the zone fit/score
  (``nodenumaresource/scoring.go:55``), device count-fit
  (``deviceshare/device_cache.go:329-352``), and reservation nomination
  (``reservation/scoring.go:42,105,177``) semantics.

File format (little-endian): magic ``KEXT1\n``, then per section a
u32 name length, the name, u32 ndim, i64 dims, and the row-major i64
payload (bools/i32 widened to i64).
"""

from __future__ import annotations

import struct
from typing import Dict, List, Tuple

import numpy as np

from koordinator_tpu.model import resources as res
from koordinator_tpu.model.device import (
    DEVICE_RESOURCE_AXIS,
    DeviceBatch,
    encode_devices,
)
from koordinator_tpu.model.reservation import (
    ReservationTable,
    encode_reservations,
)
from koordinator_tpu.model.topology import ZoneBatch, encode_zones

Gi = 1 << 30
Mi = 1 << 20

# canonical device-axis projection: C column -> snapshot resource index
DEV_AXIS = [res.RESOURCE_INDEX[n] for n in DEVICE_RESOURCE_AXIS]


def extras_scenario(
    nodes: List[Dict],
    pods: List[Dict],
    seed: int = 0,
    node_bucket: int = 0,
    pod_bucket: int = 0,
) -> Tuple[ZoneBatch, np.ndarray, DeviceBatch, ReservationTable, List[Dict], List[Dict]]:
    """Deterministic extras tables for an existing node/pod list, plus
    the MUTATED node/pod lists that make every plugin leg load-bearing
    (callers must encode the snapshot from the returned lists):

    * every node gets 2 NUMA zones splitting its FULL allocatable vector
      (cpu, memory, pods, device axes — ``zone_fit_mask`` checks every
      requested axis), with a policy mix over the node index
      (none / best-effort / restricted / single-numa-node);
    * every 4th node carries 4 GPU minors (some partially used) and one
      RDMA NIC, and advertises the device resources in its allocatable;
    * every 8th pod requests one GPU card (every 32nd two cards, every
      64th also an RDMA share), so device count-fit and scoreNode have
      real work on both implementations;
    * one reservation per 16th node, matched to every 8th pod.

    Returns ``(zones, policy, devices, rsv, nodes_out, pods_out)``.
    """
    from koordinator_tpu.model.snapshot import pad_bucket

    rng = np.random.RandomState(seed)
    N = len(nodes)
    P = len(pods)
    node_bucket = node_bucket or pad_bucket(N)
    pod_bucket = pod_bucket or pad_bucket(P)

    # device-carrying nodes advertise the resources node-level (the
    # reference's device webhook patches Node status the same way)
    nodes_out: List[Dict] = []
    for i, nd in enumerate(nodes):
        nd = dict(nd)
        if i % 4 == 0:
            alloc = dict(nd["allocatable"])
            alloc["koordinator.sh/gpu-core"] = 400
            alloc["koordinator.sh/gpu-memory"] = 4 * 16 * Gi
            alloc["koordinator.sh/gpu-memory-ratio"] = 400
            alloc["koordinator.sh/rdma"] = 100
            nd["allocatable"] = alloc
        nodes_out.append(nd)

    # every 8th pod requests a GPU card; the koordlet-side webhook fills
    # memory from ratio, so ratio+core is the canonical request shape
    pods_out: List[Dict] = []
    for p, pod in enumerate(pods):
        pod = dict(pod)
        if p % 8 == 0:
            reqs = dict(pod.get("requests", {}))
            cards = 2 if p % 32 == 0 else 1
            reqs["koordinator.sh/gpu-core"] = 100 * cards
            reqs["koordinator.sh/gpu-memory-ratio"] = 100 * cards
            if p % 64 == 0:
                reqs["koordinator.sh/rdma"] = 50
            pod["requests"] = reqs
        pods_out.append(pod)

    zone_specs = []
    for i, nd in enumerate(nodes_out):
        full = res.resource_vector(nd["allocatable"])
        used_cpu = int(rng.randint(0, max(full[res.RESOURCE_INDEX[res.CPU]] // 4, 1)))
        # axis units (cpu milli, MiB) back through format_quantity so
        # encode_zones' resource_vector round-trips them exactly
        half0 = {
            name: res.format_quantity(int(full[res.RESOURCE_INDEX[name]]) // 2, name)
            for name in res.RESOURCE_AXIS
            if full[res.RESOURCE_INDEX[name]]
        }
        half1 = {
            name: res.format_quantity(
                int(full[res.RESOURCE_INDEX[name]])
                - int(full[res.RESOURCE_INDEX[name]]) // 2,
                name,
            )
            for name in res.RESOURCE_AXIS
            if full[res.RESOURCE_INDEX[name]]
        }
        zones = [
            {"allocatable": half0, "requested": {"cpu": f"{used_cpu}m"}},
            {"allocatable": half1, "requested": {}},
        ]
        zone_specs.append({"zones": zones})
    zbatch = encode_zones(zone_specs, node_bucket=node_bucket)
    policy = np.asarray(
        [i % 4 for i in range(N)] + [0] * (node_bucket - N), np.int32
    )

    dev_specs = []
    for i in range(N):
        devs = []
        if i % 4 == 0:
            for m in range(4):
                free_core = 100 if (i + m) % 3 else 40
                devs.append(
                    {
                        "type": "gpu",
                        "minor": m,
                        "total": {
                            "koordinator.sh/gpu-core": 100,
                            "koordinator.sh/gpu-memory": 16 * Gi,
                            "koordinator.sh/gpu-memory-ratio": 100,
                        },
                        "free": {
                            "koordinator.sh/gpu-core": free_core,
                            "koordinator.sh/gpu-memory": 16 * Gi * free_core // 100,
                            "koordinator.sh/gpu-memory-ratio": free_core,
                        },
                        "topology": {"numaNode": m // 2},
                    }
                )
            devs.append(
                {
                    "type": "rdma",
                    "minor": 0,
                    "total": {"koordinator.sh/rdma": 100},
                    "free": {"koordinator.sh/rdma": 100},
                    "topology": {"numaNode": 0},
                }
            )
        dev_specs.append({"devices": devs})
    dbatch = encode_devices(dev_specs, node_bucket=node_bucket)

    # reservations match pods by owner label selector (the reference's
    # MatchReservationOwners label path); tag every 8th pod round-robin
    rsv_specs = []
    node_names = [nd["name"] for nd in nodes_out]
    n_rsv = max(1, len(range(0, N, 16)))
    for k, i in enumerate(range(0, N, 16)):
        rsv_specs.append(
            {
                "name": f"rsv-{k}",
                "node": node_names[i],
                "allocatable": {"cpu": "4000m", "memory": 8 * Gi},
                "allocated": {"cpu": "1000m", "memory": 2 * Gi},
                "allocate_policy": "Aligned" if i % 32 else "Default",
                "order": (k + 1) if i % 48 == 0 else 0,
                "owners": [{"label_selector": {"rsv-owner": f"rsv-{k}"}}],
                "labels": {
                    "reservation-type": "gold" if k % 2 == 0 else "general"
                },
            }
        )
    for p, pod in enumerate(pods_out):
        if p % 8 == 0:
            labels = dict(pod.get("labels", {}))
            labels["rsv-owner"] = f"rsv-{(p // 8) % n_rsv}"
            pod["labels"] = labels
        if p % 16 == 0 and n_rsv > 1:
            # required reservation affinity (reference exact key): these
            # pods may only land on nodes holding a matched gold-labeled
            # reservation — the affinity filter leg is load-bearing
            anns = dict(pod.get("annotations", {}))
            anns["scheduling.koordinator.sh/reservation-affinity"] = {
                "reservationSelector": {"reservation-type": "gold"}
            }
            pod["annotations"] = anns
    rsv = encode_reservations(
        rsv_specs, pods_out, node_names, pod_bucket=pod_bucket
    )
    return zbatch, policy, dbatch, rsv, nodes_out, pods_out


def plugin_extra_tensors(snapshot, zones, policy, devices, rsv, cfg=None):
    """Compose the real plugins into (extra_mask, extra_scores) — the
    exact tensors ``FrameworkExtender.run_cycle`` would feed the solver.

    The composition runs as ONE jitted program: eagerly, the [P, N, Z, R]
    zone broadcast materializes multi-GB intermediates at the 10k x 2k
    benchmark shape (and every eager op is its own launch); fused, XLA
    keeps only the [P, N] outputs hot."""
    import jax
    import jax.numpy as jnp

    from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG
    from koordinator_tpu.scheduler.framework import CycleContext, FrameworkExtender
    from koordinator_tpu.scheduler.plugins import (
        DeviceSharePlugin,
        NodeNUMAResourcePlugin,
        ReservationPlugin,
    )

    cfg = cfg or DEFAULT_CYCLE_CONFIG

    @jax.jit
    def compose(snapshot, zones, policy, devices, rsv):
        ctx = CycleContext(
            snapshot=snapshot,
            cfg=cfg,
            extras={
                "zones": zones,
                "numa_policy": policy,
                "devices": devices,
                "reservations": rsv,
            },
        )
        fx = FrameworkExtender(
            plugins=[
                NodeNUMAResourcePlugin(),
                ReservationPlugin(),
                DeviceSharePlugin(),
            ]
        )
        mask, scores, _ = fx.extended_tensors(ctx)
        return mask, scores

    return compose(snapshot, zones, jnp.asarray(policy), devices, rsv)


def _section(name: str, arr: np.ndarray) -> bytes:
    arr = np.ascontiguousarray(arr, np.int64)
    head = struct.pack("<I", len(name)) + name.encode()
    head += struct.pack("<I", arr.ndim)
    head += np.asarray(arr.shape, "<i8").tobytes()
    return head + arr.astype("<i8").tobytes()


def write_extras_file(
    path: str,
    zones: ZoneBatch,
    policy: np.ndarray,
    devices: DeviceBatch,
    rsv: ReservationTable,
    fit_weights: np.ndarray,
) -> None:
    sections = {
        "fit_weights": np.asarray(fit_weights),
        "zone_alloc": np.asarray(zones.allocatable),
        "zone_req": np.asarray(zones.requested),
        "zone_valid": np.asarray(zones.valid),
        "numa_policy": np.asarray(policy),
        "dev_total": np.asarray(devices.total),
        "dev_free": np.asarray(devices.free),
        "dev_type": np.asarray(devices.dev_type),
        "dev_valid": np.asarray(devices.valid),
        "dev_axis": np.asarray(DEV_AXIS),
        "rsv_node": np.asarray(rsv.node_index),
        "rsv_allocatable": np.asarray(rsv.allocatable),
        "rsv_allocated": np.asarray(rsv.allocated),
        "rsv_declared": np.asarray(rsv.declared),
        "rsv_policy": np.asarray(rsv.allocate_policy),
        "rsv_order": np.asarray(rsv.order),
        "rsv_unschedulable": np.asarray(rsv.unschedulable),
        "rsv_valid": np.asarray(rsv.valid),
        "rsv_matched": np.asarray(rsv.matched),
        "rsv_affinity_required": (
            np.asarray(rsv.affinity_required)
            if rsv.affinity_required is not None
            else np.zeros(np.asarray(rsv.matched).shape[0], bool)
        ),
    }
    with open(path, "wb") as f:
        f.write(b"KEXT1\n")
        for name, arr in sections.items():
            f.write(_section(name, arr))
