"""Fixed-shape cluster snapshot: the contract everything compiles against.

A scheduling cycle's world state — nodes (allocatable / requested / measured
usage), pending pods (requests / estimated usage / QoS / priority / gang /
quota membership) — is encoded as dense, padded int64 arrays so that one
``jax.jit``-compiled program scores and assigns every pending pod against
every candidate node at once.  This mirrors the semantics of the reference's
data model (reference ``apis/extension/qos.go:22``, ``priority.go:29``,
``resource.go:26``) without its per-object Go representation.

Pod/node counts vary per cycle; arrays are padded to shape *buckets*
(powers of two by default) so XLA compiles one program per bucket instead of
one per cycle (reference analog: the Go scheduler has no compile step; for
XLA this padding is what keeps the hot path recompile-free).

Estimator semantics (``estimated`` field) follow the reference's
defaultEstimator exactly (reference
``pkg/scheduler/plugins/loadaware/estimator/default_estimator.go:81-127``):
``max(request, limit)`` scaled by per-resource factors, with 250m CPU /
200MiB defaults for unset requests, translated to batch-/mid- resources by
priority class (reference ``apis/extension/resource.go:53``).
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.model import resources as res

MAX_NODE_SCORE = 100  # k8s framework.MaxNodeScore

DEFAULT_MILLI_CPU_REQUEST = 250  # default_estimator.go:36
DEFAULT_MEMORY_REQUEST = 200  # default_estimator.go:38: 200Mi, on the MiB axis

# v1beta2/defaults.go:35-48
DEFAULT_RESOURCE_WEIGHTS = {res.CPU: 1, res.MEMORY: 1}
DEFAULT_USAGE_THRESHOLDS = {res.CPU: 65, res.MEMORY: 95}
DEFAULT_ESTIMATED_SCALING_FACTORS = {res.CPU: 85, res.MEMORY: 70}
DEFAULT_NODE_METRIC_EXPIRATION_SECONDS = 180

# NodeMetric aggregated-usage percentiles carried by NodeBatch.agg_usage
# (reference slov1alpha1 AggregationType; the statesinformer aggregates
# these four windows, ``impl/states_nodemetric.go:324``)
PERCENTILES = ("p50", "p90", "p95", "p99")


class PriorityClass(enum.IntEnum):
    """Koordinator priority bands (reference apis/extension/priority.go:29)."""

    PROD = 0
    MID = 1
    BATCH = 2
    FREE = 3
    NONE = 4

    @classmethod
    def from_name(cls, name: Optional[str]) -> "PriorityClass":
        return {
            "koord-prod": cls.PROD,
            "koord-mid": cls.MID,
            "koord-batch": cls.BATCH,
            "koord-free": cls.FREE,
        }.get(name or "", cls.NONE)

    @classmethod
    def from_priority_value(cls, priority: Optional[int]) -> "PriorityClass":
        if priority is None:
            return cls.NONE
        for lo, hi, pc in PRIORITY_BANDS:
            if lo <= priority <= hi:
                return pc
        return cls.NONE


# reference apis/extension/priority.go:84-101: inclusive priority-value
# bands; a value in none of them is NONE.  The one table both the scalar
# and the column derivation read.
PRIORITY_BANDS = (
    (9000, 9999, PriorityClass.PROD),
    (7000, 7999, PriorityClass.MID),
    (5000, 5999, PriorityClass.BATCH),
    (3000, 3999, PriorityClass.FREE),
)


def _band_lookup() -> Tuple[np.ndarray, np.ndarray]:
    """Sorted band edges and the class of each interval between them
    (NONE outside every band), for ``np.searchsorted``."""
    edges, classes = [], [int(PriorityClass.NONE)]
    for lo, hi, pc in sorted(PRIORITY_BANDS):
        edges += [lo, hi + 1]
        classes += [int(pc), int(PriorityClass.NONE)]
    return np.asarray(edges, np.int64), np.asarray(classes, np.int32)


_BAND_EDGES, _BAND_CLASSES = _band_lookup()


def priority_class_column(priority) -> np.ndarray:
    """``PriorityClass.from_priority_value`` over a whole column of int64
    priority values (array or list): one band lookup, int32 out."""
    idx = np.searchsorted(
        _BAND_EDGES, np.asarray(priority, np.int64), side="right"
    )
    return _BAND_CLASSES[idx]


class QoSClass(enum.IntEnum):
    """Koordinator QoS classes (reference apis/extension/qos.go:22-28)."""

    LSE = 0
    LSR = 1
    LS = 2
    BE = 3
    SYSTEM = 4
    NONE = 5

    @classmethod
    def from_name(cls, name: Optional[str]) -> "QoSClass":
        return {
            "LSE": cls.LSE,
            "LSR": cls.LSR,
            "LS": cls.LS,
            "BE": cls.BE,
            "SYSTEM": cls.SYSTEM,
        }.get(name or "", cls.NONE)


# PriorityClass -> {native resource index -> translated resource index},
# reference apis/extension/resource.go:40-49.
_RESOURCE_TRANSLATION = {
    PriorityClass.BATCH: {
        res.RESOURCE_INDEX[res.CPU]: res.RESOURCE_INDEX[res.BATCH_CPU],
        res.RESOURCE_INDEX[res.MEMORY]: res.RESOURCE_INDEX[res.BATCH_MEMORY],
    },
    PriorityClass.MID: {
        res.RESOURCE_INDEX[res.CPU]: res.RESOURCE_INDEX[res.MID_CPU],
        res.RESOURCE_INDEX[res.MEMORY]: res.RESOURCE_INDEX[res.MID_MEMORY],
    },
}


def translate_resource_index(priority_class: PriorityClass, idx: int) -> int:
    """reference apis/extension/resource.go:53 TranslateResourceNameByPriorityClass."""
    if priority_class in (PriorityClass.PROD, PriorityClass.NONE):
        return idx
    return _RESOURCE_TRANSLATION.get(priority_class, {}).get(idx, idx)


def pad_bucket(n: int, minimum: int = 8) -> int:
    """Smallest power-of-two bucket >= n (>= minimum)."""
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class NodeBatch:
    """Dense node-side state, shapes [N] / [N, R]."""

    allocatable: jnp.ndarray  # i64[N, R] node allocatable (estimator-adjusted)
    requested: jnp.ndarray  # i64[N, R] sum of scheduled pod requests (Fit accounting)
    usage: jnp.ndarray  # i64[N, R] measured usage from NodeMetric
    metric_fresh: jnp.ndarray  # bool[N] NodeMetric exists and is not expired
    valid: jnp.ndarray  # bool[N] padding mask
    # LoadAware aggregated/prod extensions (reference
    # ``plugins/loadaware/load_aware.go:150-226,291-311``; None = the node
    # source reported no such data and the plain tensors apply):
    # aggregated usage percentiles, axis order config.PERCENTILES
    agg_usage: "jnp.ndarray | None" = None  # i64[N, A, R]
    # which (node, percentile) cells carry real data — a node may report
    # only some percentiles; missing ones fall back like the reference's
    # nil getTargetAggregatedUsage (filter passes, score uses plain usage)
    agg_fresh: "jnp.ndarray | None" = None  # bool[N, A]
    prod_usage: "jnp.ndarray | None" = None  # i64[N, R] sum of prod pods' usage
    # accelerator type per node (ISSUE 15 heterogeneity term): index
    # into the throughput matrix's accelerator axis; None = never
    # synced (the term treats every node as type 0)
    accel_type: "jnp.ndarray | None" = None  # i32[N]
    names: Tuple[str, ...] = ()

    @property
    def capacity(self) -> int:
        return self.allocatable.shape[0]


@dataclasses.dataclass
class PodBatch:
    """Dense pending-pod state, shapes [P] / [P, R]."""

    requests: jnp.ndarray  # i64[P, R] raw requests (Fit accounting)
    estimated: jnp.ndarray  # i64[P, R] LoadAware estimator output
    priority_class: jnp.ndarray  # i32[P] PriorityClass enum
    qos: jnp.ndarray  # i32[P] QoSClass enum
    priority: jnp.ndarray  # i32[P] raw pod priority value (queue order)
    gang_id: jnp.ndarray  # i32[P] index into GangTable, -1 = no gang
    quota_id: jnp.ndarray  # i32[P] index into QuotaTable, -1 = no quota
    valid: jnp.ndarray  # bool[P] padding mask
    # fused-term pod columns (ISSUE 15): workload class indexes the
    # throughput matrix's class axis (heterogeneity); sensitivity is the
    # Synergy-style per-resource profile in [0, 100].  None = never
    # synced — the terms are inert for the missing half.
    workload_class: "jnp.ndarray | None" = None  # i32[P]
    sensitivity: "jnp.ndarray | None" = None  # i64[P, R]
    names: Tuple[str, ...] = ()

    @property
    def capacity(self) -> int:
        return self.requests.shape[0]


@dataclasses.dataclass
class GangTable:
    """Coscheduling PodGroups (reference plugins/coscheduling/core/core.go:220).

    ``min_member`` is the gang's minMember; a gang admits only if at least
    that many members can be placed in the same cycle (all-or-nothing mask).
    """

    min_member: jnp.ndarray  # i32[G]
    valid: jnp.ndarray  # bool[G]
    names: Tuple[str, ...] = ()


@dataclasses.dataclass
class QuotaTable:
    """Flattened ElasticQuota groups after host-side runtime fair division.

    ``runtime`` is each group's runtimeQuota per resource, computed by
    ``koordinator_tpu.constraints.quota`` with the same redistribution rule
    as the reference (``elasticquota/core/runtime_quota_calculator.go:126``);
    ``used`` is current usage.  The device-side mask admits a pod onto any
    node only while its quota group stays within runtime.
    """

    runtime: jnp.ndarray  # i64[Q, R]
    used: jnp.ndarray  # i64[Q, R]
    limited: jnp.ndarray  # bool[Q, R] quota declares this dimension
    valid: jnp.ndarray  # bool[Q]
    names: Tuple[str, ...] = ()


@dataclasses.dataclass
class ClusterSnapshot:
    nodes: NodeBatch
    pods: PodBatch
    gangs: GangTable
    quotas: QuotaTable
    # per-(workload class, accelerator type) throughput matrix
    # (ISSUE 15 heterogeneity term, Gavel 2008.09213): [C, A] i64 with
    # values normalized to [0, MAX_NODE_SCORE]; replicated over the
    # cluster mesh.  None = the term has no data and contributes nothing.
    throughput: "jnp.ndarray | None" = None

    @property
    def num_nodes(self) -> int:
        return int(np.asarray(self.nodes.valid).sum())

    @property
    def num_pods(self) -> int:
        return int(np.asarray(self.pods.valid).sum())


# Snapshot containers cross the jit boundary: register as pytrees with the
# host-side name tuples as static aux data.
for _cls, _data in (
    (
        NodeBatch,
        [
            "allocatable",
            "requested",
            "usage",
            "metric_fresh",
            "valid",
            "agg_usage",
            "agg_fresh",
            "prod_usage",
            "accel_type",
        ],
    ),
    (
        PodBatch,
        [
            "requests",
            "estimated",
            "priority_class",
            "qos",
            "priority",
            "gang_id",
            "quota_id",
            "valid",
            "workload_class",
            "sensitivity",
        ],
    ),
    (GangTable, ["min_member", "valid"]),
    (QuotaTable, ["runtime", "used", "limited", "valid"]),
):
    # names are static metadata ON PURPOSE for the embedded API (reply
    # assembly reads them host-side); the hot bridge path strips them to
    # () before any jit sees the snapshot (bridge/state.py builds every
    # resident table with names=()), so the jit cache never keys on them
    jax.tree_util.register_dataclass(  # koordlint: disable=retrace-hazard(names stripped on the resident path; embedded API only)
        _cls, data_fields=_data, meta_fields=["names"]
    )
jax.tree_util.register_dataclass(
    ClusterSnapshot,
    data_fields=["nodes", "pods", "gangs", "quotas", "throughput"],
    meta_fields=[],
)


# ---------------------------------------------------------------------------
# Host-side estimator (exact integer parity with default_estimator.go)
# ---------------------------------------------------------------------------


def _estimated_used_by_resource(
    request: int, limit: int, default_value: int, scaling_factor: int
) -> int:
    """default_estimator.go:81-127 estimatedUsedByResource, one resource."""
    if limit > request:
        scaling_factor = 100
        quantity = limit
    else:
        quantity = request
    if quantity == 0:
        return default_value
    # Go: int64(math.Round(float64(q) * float64(factor) / 100)); math.Round
    # rounds half away from zero (quantities are non-negative here), unlike
    # Python's banker's round().
    estimated = int(math.floor(quantity * scaling_factor / 100 + 0.5))
    if limit > 0 and estimated > limit:
        estimated = limit
    return estimated


def estimate_pod(
    requests_vec: Sequence[int],
    limits_vec: Sequence[int],
    priority_class: PriorityClass,
    resource_weights: Mapping[str, int] = DEFAULT_RESOURCE_WEIGHTS,
    scaling_factors: Mapping[str, int] = DEFAULT_ESTIMATED_SCALING_FACTORS,
) -> List[int]:
    """defaultEstimator.EstimatePod (default_estimator.go:58-73), dense form.

    Returns estimated used in the *weighted* (native) resource slots; the
    lookup reads the priority-translated slot of requests/limits.
    """
    out = [0] * res.NUM_RESOURCES
    for name, _w in resource_weights.items():
        idx = res.RESOURCE_INDEX[name]
        real_idx = translate_resource_index(priority_class, idx)
        if res.RESOURCE_AXIS[real_idx] in (res.CPU, res.BATCH_CPU):
            default_value = DEFAULT_MILLI_CPU_REQUEST
        elif res.RESOURCE_AXIS[real_idx] in (res.MEMORY, res.BATCH_MEMORY):
            default_value = DEFAULT_MEMORY_REQUEST
        else:
            default_value = 0
        out[idx] = _estimated_used_by_resource(
            requests_vec[real_idx],
            limits_vec[real_idx],
            default_value,
            # A weighted resource with no scaling-factor entry estimates 0
            # (Go map zero-value in estimatedPodUsed, default_estimator.go:67).
            int(scaling_factors.get(name, 0)),
        )
    return out


# ---------------------------------------------------------------------------
# Encoders
# ---------------------------------------------------------------------------


def encode_snapshot(
    nodes: Sequence[Mapping],
    pods: Sequence[Mapping],
    gangs: Sequence[Mapping] = (),
    quotas: Sequence[Mapping] = (),
    *,
    resource_weights: Mapping[str, int] = DEFAULT_RESOURCE_WEIGHTS,
    scaling_factors: Mapping[str, int] = DEFAULT_ESTIMATED_SCALING_FACTORS,
    node_bucket: Optional[int] = None,
    pod_bucket: Optional[int] = None,
    throughput: Optional[Sequence[Sequence[int]]] = None,
) -> ClusterSnapshot:
    """Encode plain-dict cluster state into a padded ClusterSnapshot.

    Node dict: ``{"name", "allocatable": {res: qty}, "requested": {...},
    "usage": {...}, "metric_fresh": bool}``.
    Pod dict: ``{"name", "requests": {...}, "limits": {...},
    "priority_class": "koord-prod"|..., "priority": int, "qos": "LS"|...,
    "gang": gang-name|None, "quota": quota-name|None}``.
    Gang dict: ``{"name", "min_member": int}``.
    Quota dict: ``{"name", "runtime": {...}, "used": {...}}`` (runtime from
    ``constraints.quota.refresh_runtime``).

    Fused-term data (ISSUE 15; all optional — the resulting leaves stay
    None when no input mentions them, so existing callers' snapshot
    structure is unchanged): node ``"accel_type"`` (int), pod
    ``"workload_class"`` (int) and ``"sensitivity"`` ({res: 0..100}),
    and the ``throughput`` [C, A] matrix keyword.
    """
    n_bucket = node_bucket or pad_bucket(len(nodes))
    p_bucket = pod_bucket or pad_bucket(len(pods))
    g_bucket = pad_bucket(max(len(gangs), 1))
    q_bucket = pad_bucket(max(len(quotas), 1))
    R = res.NUM_RESOURCES

    gang_index = {g["name"]: i for i, g in enumerate(gangs)}
    quota_index = {q["name"]: i for i, q in enumerate(quotas)}

    node_alloc = np.zeros((n_bucket, R), np.int64)
    node_req = np.zeros((n_bucket, R), np.int64)
    node_usage = np.zeros((n_bucket, R), np.int64)
    node_fresh = np.zeros((n_bucket,), bool)
    node_valid = np.zeros((n_bucket,), bool)
    n_pct = len(PERCENTILES)
    node_agg = np.zeros((n_bucket, n_pct, R), np.int64)
    node_agg_fresh = np.zeros((n_bucket, n_pct), bool)
    node_prod = np.zeros((n_bucket, R), np.int64)
    node_accel = np.zeros((n_bucket,), np.int32)
    any_accel = any("accel_type" in nd for nd in nodes)
    for i, nd in enumerate(nodes):
        node_alloc[i] = res.resource_vector(nd.get("allocatable", {}))
        node_req[i] = res.resource_vector(nd.get("requested", {}))
        node_usage[i] = res.resource_vector(nd.get("usage", {}))
        node_fresh[i] = bool(nd.get("metric_fresh", True))
        node_valid[i] = True
        # aggregated percentile usage: {"p50": {res: qty}, ...} — nodes
        # whose koordlet reported AggregatedNodeUsages
        agg = nd.get("agg_usage")
        if agg:
            for a, pct in enumerate(PERCENTILES):
                if pct in agg:
                    node_agg[i, a] = res.resource_vector(agg[pct])
                    node_agg_fresh[i, a] = True
        if nd.get("prod_usage") is not None:
            node_prod[i] = res.resource_vector(nd["prod_usage"])
        if nd.get("accel_type") is not None:
            node_accel[i] = int(nd["accel_type"])

    pod_req = np.zeros((p_bucket, R), np.int64)
    pod_est = np.zeros((p_bucket, R), np.int64)
    pod_prio_class = np.full((p_bucket,), int(PriorityClass.NONE), np.int32)
    pod_qos = np.full((p_bucket,), int(QoSClass.NONE), np.int32)
    pod_prio = np.zeros((p_bucket,), np.int32)
    pod_gang = np.full((p_bucket,), -1, np.int32)
    pod_quota = np.full((p_bucket,), -1, np.int32)
    pod_valid = np.zeros((p_bucket,), bool)
    pod_wclass = np.zeros((p_bucket,), np.int32)
    pod_sens = np.zeros((p_bucket, R), np.int64)
    any_wclass = any("workload_class" in pd for pd in pods)
    any_sens = any("sensitivity" in pd for pd in pods)
    for i, pd in enumerate(pods):
        req_vec = res.resource_vector(pd.get("requests", {}))
        lim_vec = res.resource_vector(pd.get("limits", {}))
        pc = pd.get("priority_class")
        if pc is not None:
            prio_class = PriorityClass.from_name(pc)
        else:
            prio_class = PriorityClass.from_priority_value(pd.get("priority"))
        pod_req[i] = req_vec
        pod_est[i] = estimate_pod(
            req_vec, lim_vec, prio_class, resource_weights, scaling_factors
        )
        pod_prio_class[i] = int(prio_class)
        pod_qos[i] = int(QoSClass.from_name(pd.get("qos")))
        pod_prio[i] = int(pd.get("priority") or 0)
        # Unknown gang/quota names (object not yet synced into the snapshot)
        # degrade to "no gang"/"no quota" rather than crashing the encode.
        if pd.get("gang") is not None:
            pod_gang[i] = gang_index.get(pd["gang"], -1)
        if pd.get("quota") is not None:
            pod_quota[i] = quota_index.get(pd["quota"], -1)
        if pd.get("workload_class") is not None:
            pod_wclass[i] = int(pd["workload_class"])
        if pd.get("sensitivity") is not None:
            pod_sens[i] = res.resource_vector(pd["sensitivity"])
        pod_valid[i] = True

    gang_min = np.zeros((g_bucket,), np.int32)
    gang_valid = np.zeros((g_bucket,), bool)
    for i, g in enumerate(gangs):
        gang_min[i] = int(g.get("min_member", 0))
        gang_valid[i] = True

    quota_runtime = np.zeros((q_bucket, R), np.int64)
    quota_used = np.zeros((q_bucket, R), np.int64)
    quota_limited = np.zeros((q_bucket, R), bool)
    quota_valid = np.zeros((q_bucket,), bool)
    for i, q in enumerate(quotas):
        quota_runtime[i] = res.resource_vector(q.get("runtime", {}))
        quota_used[i] = res.resource_vector(q.get("used", {}))
        # A quota constrains only the dimensions it declares (the reference
        # checks used+request against runtime only for the quota's declared
        # resource dimensions, elasticquota plugin PreFilter).  "limited"
        # lists the declared dims explicitly so a zero-runtime dimension
        # still rejects (the reference keeps declared dims in the runtime
        # list with explicit zeros; only undeclared dims fall open).
        for name in q.get("limited", q.get("runtime", {})):
            idx = res.RESOURCE_INDEX.get(name)
            if idx is not None:
                quota_limited[i, idx] = True
        quota_valid[i] = True

    return ClusterSnapshot(
        nodes=NodeBatch(
            allocatable=jnp.asarray(node_alloc),
            requested=jnp.asarray(node_req),
            usage=jnp.asarray(node_usage),
            metric_fresh=jnp.asarray(node_fresh),
            valid=jnp.asarray(node_valid),
            agg_usage=jnp.asarray(node_agg),
            agg_fresh=jnp.asarray(node_agg_fresh),
            prod_usage=jnp.asarray(node_prod),
            accel_type=jnp.asarray(node_accel) if any_accel else None,
            names=tuple(nd.get("name", f"node-{i}") for i, nd in enumerate(nodes)),
        ),
        pods=PodBatch(
            requests=jnp.asarray(pod_req),
            estimated=jnp.asarray(pod_est),
            priority_class=jnp.asarray(pod_prio_class),
            qos=jnp.asarray(pod_qos),
            priority=jnp.asarray(pod_prio),
            gang_id=jnp.asarray(pod_gang),
            quota_id=jnp.asarray(pod_quota),
            valid=jnp.asarray(pod_valid),
            workload_class=jnp.asarray(pod_wclass) if any_wclass else None,
            sensitivity=jnp.asarray(pod_sens) if any_sens else None,
            names=tuple(pd.get("name", f"pod-{i}") for i, pd in enumerate(pods)),
        ),
        gangs=GangTable(
            min_member=jnp.asarray(gang_min),
            valid=jnp.asarray(gang_valid),
            names=tuple(g["name"] for g in gangs),
        ),
        quotas=QuotaTable(
            runtime=jnp.asarray(quota_runtime),
            used=jnp.asarray(quota_used),
            limited=jnp.asarray(quota_limited),
            valid=jnp.asarray(quota_valid),
            names=tuple(q["name"] for q in quotas),
        ),
        throughput=(
            jnp.asarray(np.asarray(throughput, np.int64))
            if throughput is not None
            else None
        ),
    )
