"""Multi-chip scale-out of the scoring/assignment tensors.

The reference scales the Score phase by fanning goroutines over nodes on one
machine (``frameworkext/framework_extender.go:216``).  The TPU-native scale
axis is a ``jax.sharding.Mesh``:

* ``pods`` mesh axis — data-parallel analog: each chip scores a slice of the
  pending-pod batch.
* ``nodes`` mesh axis — model-parallel analog: node state (allocatable /
  requested / usage) is sharded so clusters larger than one chip's HBM
  spread across ICI neighbors; argmax-over-nodes becomes an XLA collective.

One ``pods x nodes`` score tensor sharded over a 2-D mesh keeps all
collectives on ICI (scaling-book recipe: annotate shardings, let XLA insert
the collectives).  The sequential greedy scan shards node state over the
whole mesh and keeps per-pod rows replicated — each scan step's
argmax(masked score) then runs as a sharded reduce.
"""

from __future__ import annotations

from dataclasses import replace as dataclass_replace
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.model.snapshot import ClusterSnapshot

# the one mesh axis of the RESIDENT cluster: node rows spread over it,
# pod rows and the gang/quota tables replicate (ISSUE 7).  Distinct from
# make_mesh's 2-D scoring mesh: the resident snapshot's capacity axis is
# nodes — that is the tensor that outgrows one chip's HBM first (the
# 100k x 10k fp32 cost tensor is ~4 GB; the node tables scale with it).
CLUSTER_AXIS = "nodes"

# the POD mesh axis of the sparse candidate engine (ISSUE 16,
# solver/candidates.py): the [P, C] candidate-index and candidate-score
# tensors split over POD rows — each device builds and scores its own
# pods' candidate lists against a REPLICATED node table, so the sparse
# pipeline runs with zero collectives.  Orthogonal to CLUSTER_AXIS: the
# dense residency scales the node axis, the sparse engine scales pods.
POD_AXIS = "pods"


def cluster_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """The 1-D resident-snapshot mesh: every device holds one node-axis
    shard of the cluster.  ``devices`` defaults to all visible devices;
    pass a prefix (``jax.devices()[:k]``) to shard over fewer chips."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (CLUSTER_AXIS,))


def pow2_device_count(n: int) -> int:
    """Largest power of two <= ``n`` (>= 1).  Node buckets are powers of
    two, so only a power-of-two mesh size is guaranteed to divide every
    geometry — a 6-device cluster mesh would never activate (the
    resident state falls back to single-chip placement on every
    bucket); daemons round their device count down through this before
    building the mesh."""
    n = max(1, int(n))
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def node_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NamedSharding for a node-major tensor ([N], [N, R], [N, A, R]):
    leading axis split over the cluster mesh, trailing axes whole."""
    return NamedSharding(
        mesh, P(CLUSTER_AXIS, *([None] * (ndim - 1)))
    )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def score_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding of the resident [P, N] score/feasible tensors
    (ISSUE 9): the NODE axis (axis 1) splits over the cluster mesh —
    column j lives on the device owning node j's snapshot rows, so the
    incremental dirty-column rescore is shard-local exactly like the
    delta scatter, and the persistent score tensor's HBM cost divides
    by the mesh like the node tables it derives from."""
    return NamedSharding(mesh, P(None, CLUSTER_AXIS))


def snapshot_partition_specs(snap: ClusterSnapshot):
    """A pytree of bare ``PartitionSpec``s matching ``snap``
    leaf-for-leaf — the mesh-independent half of the placement policy
    (:func:`snapshot_shardings` binds it to a mesh).  Consumed as
    ``shard_map`` in/out specs by the incremental score engine
    (solver/incremental.py), so the rescore partitions the snapshot
    exactly as it is resident — no hidden resharding program."""
    node = lambda a: P(CLUSTER_AXIS, *([None] * (np.ndim(a) - 1)))
    rep = lambda a: P()
    return _snapshot_spec_tree(snap, node, rep)


def snapshot_shardings(snap: ClusterSnapshot, mesh: Mesh):
    """A pytree of ``NamedSharding`` specs matching ``snap`` leaf-for-leaf:
    node tensors sharded along the cluster axis, pod rows and the
    gang/quota tables replicated.  ``jax.tree_util.tree_map`` over
    ``(specs, snap)`` is how a complete snapshot lands mesh-resident
    (:func:`shard_cluster_snapshot`, the embedded-API path);
    bridge/state.py builds its resident leaves incrementally through
    the same ``node_sharding``/``replicated_sharding`` policy, and
    tests/test_mesh_resident.py asserts the two stay in lockstep —
    this function (with :func:`snapshot_partition_specs`, the same
    classification over bare ``PartitionSpec``s) is the one canonical
    statement of which leaf gets which spec."""
    node = lambda a: node_sharding(mesh, np.ndim(a))
    rep = lambda a: replicated_sharding(mesh)
    return _snapshot_spec_tree(snap, node, rep)


def _snapshot_spec_tree(snap: ClusterSnapshot, node, rep):
    """The per-leaf placement classification shared by
    :func:`snapshot_shardings` and :func:`snapshot_partition_specs`:
    ``node``/``rep`` map each array leaf to its spec."""
    nodes = snap.nodes
    return ClusterSnapshot(
        nodes=dataclass_replace(
            nodes,
            allocatable=node(nodes.allocatable),
            requested=node(nodes.requested),
            usage=node(nodes.usage),
            metric_fresh=node(nodes.metric_fresh),
            valid=node(nodes.valid),
            agg_usage=(
                None if nodes.agg_usage is None else node(nodes.agg_usage)
            ),
            agg_fresh=(
                None if nodes.agg_fresh is None else node(nodes.agg_fresh)
            ),
            prod_usage=(
                None if nodes.prod_usage is None else node(nodes.prod_usage)
            ),
            accel_type=(
                None if nodes.accel_type is None else node(nodes.accel_type)
            ),
        ),
        pods=jax.tree_util.tree_map(rep, snap.pods),
        gangs=jax.tree_util.tree_map(rep, snap.gangs),
        quotas=jax.tree_util.tree_map(rep, snap.quotas),
        # the throughput matrix (ISSUE 15) is a small [C, A] side table
        # every shard's gather reads: replicated, like the pod rows
        throughput=(
            None if snap.throughput is None else rep(snap.throughput)
        ),
    )


def shard_cluster_snapshot(snap: ClusterSnapshot, mesh: Mesh) -> ClusterSnapshot:
    """Place ``snap`` mesh-resident: one ``device_put`` per leaf with its
    :func:`snapshot_shardings` spec.  The node bucket must divide evenly
    over the mesh (buckets are powers of two — pick a power-of-two device
    count, or a prefix)."""
    n = snap.nodes.allocatable.shape[0]
    if n % mesh.size:
        raise ValueError(
            f"node bucket {n} does not divide over {mesh.size} devices; "
            "resize the mesh to a power-of-two prefix"
        )
    return jax.tree_util.tree_map(
        lambda spec, leaf: jax.device_put(leaf, spec),
        snapshot_shardings(snap, mesh),
        snap,
    )


def pod_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """The 1-D pod-axis mesh of the sparse candidate engine (ISSUE 16):
    every device owns a pod-row slice of the [P, C] candidate tensors.
    ``devices`` defaults to all visible devices; pass a power-of-two
    prefix (``jax.devices()[:pow2_device_count(n)]``) so the pod bucket
    (always a power of two) divides evenly."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (POD_AXIS,))


def sparse_score_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding of the sparse [P, C] candidate-index / score /
    feasible tensors: the POD axis (axis 0) splits over the pod mesh —
    the transpose of the dense residency's :func:`score_sharding`
    (``P(None, "nodes")``), because the sparse engine's scale axis is
    pods (C is a small static width, never worth splitting)."""
    return NamedSharding(mesh, P(POD_AXIS, None))


def snapshot_pod_partition_specs(snap: ClusterSnapshot):
    """Bare ``PartitionSpec``s placing ``snap`` for the sparse engine's
    pod-parallel shard_map: POD rows split over :data:`POD_AXIS`, node
    tables and the gang/quota/throughput side tables replicated (every
    device gathers arbitrary node rows for its own pods' candidate
    lists, so the node table must be whole on every device).  The
    mirror-image classification of :func:`snapshot_partition_specs`."""
    pod = lambda a: P(POD_AXIS, *([None] * (np.ndim(a) - 1)))
    rep = lambda a: P()
    nodes = snap.nodes
    return ClusterSnapshot(
        nodes=jax.tree_util.tree_map(rep, nodes),
        pods=dataclass_replace(
            snap.pods,
            requests=pod(snap.pods.requests),
            estimated=pod(snap.pods.estimated),
            priority_class=pod(snap.pods.priority_class),
            qos=pod(snap.pods.qos),
            priority=pod(snap.pods.priority),
            gang_id=pod(snap.pods.gang_id),
            quota_id=pod(snap.pods.quota_id),
            valid=pod(snap.pods.valid),
            workload_class=(
                None if snap.pods.workload_class is None
                else pod(snap.pods.workload_class)
            ),
            sensitivity=(
                None if snap.pods.sensitivity is None
                else pod(snap.pods.sensitivity)
            ),
        ),
        gangs=jax.tree_util.tree_map(rep, snap.gangs),
        quotas=jax.tree_util.tree_map(rep, snap.quotas),
        throughput=(
            None if snap.throughput is None else rep(snap.throughput)
        ),
    )


def _factor2(n: int):
    """Split n into (a, b) with a*b = n, as square as possible."""
    a = int(np.floor(np.sqrt(n)))
    while n % a:
        a -= 1
    return a, n // a


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    dp, tp = _factor2(len(devices))
    return Mesh(np.asarray(devices).reshape(dp, tp), ("pods", "nodes"))


def shard_snapshot_for_scoring(snap: ClusterSnapshot, mesh: Mesh) -> ClusterSnapshot:
    """Shard pods over the 'pods' axis and nodes over the 'nodes' axis.

    The resulting ``score_cycle`` output [P, N] is sharded over both mesh
    axes with zero communication (pure SPMD map).
    """
    pod2 = NamedSharding(mesh, P("pods", None))
    pod1 = NamedSharding(mesh, P("pods"))
    node2 = NamedSharding(mesh, P("nodes", None))
    node1 = NamedSharding(mesh, P("nodes"))
    rep = NamedSharding(mesh, P())

    pods = snap.pods
    nodes = snap.nodes
    return ClusterSnapshot(
        nodes=dataclass_replace(
            nodes,
            allocatable=jax.device_put(nodes.allocatable, node2),
            requested=jax.device_put(nodes.requested, node2),
            usage=jax.device_put(nodes.usage, node2),
            metric_fresh=jax.device_put(nodes.metric_fresh, node1),
            valid=jax.device_put(nodes.valid, node1),
            accel_type=(
                None if nodes.accel_type is None
                else jax.device_put(nodes.accel_type, node1)
            ),
        ),
        pods=dataclass_replace(
            pods,
            requests=jax.device_put(pods.requests, pod2),
            estimated=jax.device_put(pods.estimated, pod2),
            priority_class=jax.device_put(pods.priority_class, pod1),
            qos=jax.device_put(pods.qos, pod1),
            priority=jax.device_put(pods.priority, pod1),
            gang_id=jax.device_put(pods.gang_id, pod1),
            quota_id=jax.device_put(pods.quota_id, pod1),
            valid=jax.device_put(pods.valid, pod1),
        ),
        gangs=jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), snap.gangs),
        quotas=jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), snap.quotas),
        throughput=(
            None if snap.throughput is None
            else jax.device_put(snap.throughput, rep)
        ),
    )


def shard_snapshot_for_assign(snap: ClusterSnapshot, mesh: Mesh) -> ClusterSnapshot:
    """Shard node state across ALL mesh devices; replicate pod rows.

    The greedy scan's carried node state lives sharded; each step's
    argmax-over-nodes is a sharded reduce over ICI.
    """
    all_axes = ("pods", "nodes")
    node2 = NamedSharding(mesh, P(all_axes, None))
    node1 = NamedSharding(mesh, P(all_axes))
    rep = NamedSharding(mesh, P())

    nodes = snap.nodes
    return ClusterSnapshot(
        nodes=dataclass_replace(
            nodes,
            allocatable=jax.device_put(nodes.allocatable, node2),
            requested=jax.device_put(nodes.requested, node2),
            usage=jax.device_put(nodes.usage, node2),
            metric_fresh=jax.device_put(nodes.metric_fresh, node1),
            valid=jax.device_put(nodes.valid, node1),
            accel_type=(
                None if nodes.accel_type is None
                else jax.device_put(nodes.accel_type, node1)
            ),
        ),
        pods=jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), snap.pods),
        gangs=jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), snap.gangs),
        quotas=jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), snap.quotas),
        throughput=(
            None if snap.throughput is None
            else jax.device_put(snap.throughput, rep)
        ),
    )


