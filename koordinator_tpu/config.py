"""Cycle configuration: plugin args + weights, mirroring the reference's
component-config (reference ``pkg/scheduler/apis/config/types.go:30-205``,
defaults ``v1beta2/defaults.go:33-48``)."""

from __future__ import annotations

import dataclasses
from typing import Mapping, Tuple, Union

import jax.numpy as jnp

from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import (
    DEFAULT_ESTIMATED_SCALING_FACTORS,
    DEFAULT_RESOURCE_WEIGHTS,
    DEFAULT_USAGE_THRESHOLDS,
    PERCENTILES,
)

LEAST_ALLOCATED = "LeastAllocated"
MOST_ALLOCATED = "MostAllocated"

# Configs are passed to jax.jit as static arguments, so every field must be
# hashable: mappings are stored as sorted (name, value) tuples.
ResMap = Union[Mapping[str, int], Tuple[Tuple[str, int], ...]]


def _freeze(m: ResMap) -> Tuple[Tuple[str, int], ...]:
    if isinstance(m, tuple):
        return m
    return tuple(sorted((k, int(v)) for k, v in m.items()))


@dataclasses.dataclass(frozen=True)
class AggregatedArgs:
    """reference config.LoadAwareSchedulingAggregatedArgs (types.go:66):
    filter/score against an aggregated usage percentile instead of the
    instantaneous NodeUsage.  Durations are a host-side concern (the
    snapshot carries one aggregation window's percentiles)."""

    usage_thresholds: ResMap = ()
    usage_aggregation_type: str = "p99"
    score_aggregation_type: str = ""  # "" = score on plain NodeUsage

    def __post_init__(self):
        object.__setattr__(self, "usage_thresholds", _freeze(self.usage_thresholds))
        # either half may be disabled: empty usage type = plain-usage
        # filtering (score-only profile), empty score type = plain-usage
        # scoring — but a configured filter (thresholds) needs a percentile
        if dict(self.usage_thresholds) and self.usage_aggregation_type not in PERCENTILES:
            raise ValueError(
                "aggregated usage_thresholds need a valid "
                f"usage_aggregation_type, got {self.usage_aggregation_type!r}"
            )
        for t in (self.usage_aggregation_type, self.score_aggregation_type):
            if t and t not in PERCENTILES:
                raise ValueError(f"unknown aggregation type {t!r}")


@dataclasses.dataclass(frozen=True)
class LoadAwareArgs:
    """reference config.LoadAwareSchedulingArgs (types.go:30)."""

    resource_weights: ResMap = _freeze(DEFAULT_RESOURCE_WEIGHTS)
    usage_thresholds: ResMap = _freeze(DEFAULT_USAGE_THRESHOLDS)
    estimated_scaling_factors: ResMap = _freeze(DEFAULT_ESTIMATED_SCALING_FACTORS)
    filter_expired_node_metrics: bool = True
    node_metric_expiration_seconds: int = 180
    # aggregated-percentile profile (load_aware.go:150-224 filter path,
    # :311 scoreWithAggregation); None = plain instantaneous usage
    aggregated: "AggregatedArgs | None" = None
    # prod-pod usage thresholds: PriorityProd pods filter against the sum
    # of prod pods' usage instead of whole-node usage (:226 filterProdUsage)
    prod_usage_thresholds: ResMap = ()
    # PriorityProd pods score against prod-pods usage (:291)
    score_according_prod_usage: bool = False

    def __post_init__(self):
        object.__setattr__(self, "resource_weights", _freeze(self.resource_weights))
        object.__setattr__(self, "usage_thresholds", _freeze(self.usage_thresholds))
        object.__setattr__(
            self, "estimated_scaling_factors", _freeze(self.estimated_scaling_factors)
        )
        object.__setattr__(
            self, "prod_usage_thresholds", _freeze(self.prod_usage_thresholds)
        )


# ---------------------------------------------------------------------------
# Fused scoring-term configs (ISSUE 15).  Each term is a cellwise
# (pod row, node row) contribution fused into the ONE score_cycle launch
# (solver/terms.py holds the registry + math; docs/KERNEL.md "Scoring
# terms" has the contract).  Term configs ride CycleConfig as STATIC jit
# arguments, so every field must be hashable and every mapping must go
# through ``_freeze`` — the koordlint retrace-hazard rule checks this
# statically (an unhashable term-config field would raise at the first
# jit call; a mutable one would silently key the cache on object id).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeterogeneityTermArgs:
    """Gavel-style heterogeneity-aware scoring (PAPERS.md 2008.09213):
    a per-(workload class, accelerator type) throughput matrix rides the
    snapshot (``SyncRequest.terms.throughput``, [C, A] i64 normalized to
    [0, MAX_NODE_SCORE]); the term gathers
    ``throughput[workload_class[p], accel_type[n]]`` as a cellwise score
    so pods land where their job class runs fastest.  Device values are
    clamped to [0, MAX_NODE_SCORE] so the term's bound stays a CONFIG
    property (``weight * MAX_NODE_SCORE``) — the f32-exact serving
    top-k fast path depends on that (solver/topk.py)."""

    weight: int = 1


@dataclasses.dataclass(frozen=True)
class SensitivityTermArgs:
    """Synergy-style resource-sensitivity scoring (PAPERS.md
    2110.06073): per-pod CPU/mem sensitivity profiles
    (``PodTable.sensitivity``, [P, R] i64 in [0, 100]) replace
    GPU-proportional shares — a pod's score on a node drops with the
    node's occupancy on exactly the resources the pod is sensitive to:
    ``score = weight * (MAX_NODE_SCORE - sum_r(sens*occ)//sum_r(sens))``
    with occupancy in [0, 100] permille-free integer math.  Clamped to
    [0, weight * MAX_NODE_SCORE]."""

    weight: int = 1


@dataclasses.dataclass(frozen=True)
class PackingTermArgs:
    """Constraint-based bin packing (PAPERS.md 2511.08373): a
    MostAllocated-style objective over post-placement utilization
    (prefer filling nodes) plus an optional feasibility mask —
    ``headroom`` maps resource name -> max post-placement utilization
    PERCENT; a placement pushing a listed resource past its headroom is
    masked infeasible.  Both halves are cellwise in (pod, node): the
    mask reads only (requested[n] + req[p]) vs allocatable[n]."""

    weight: int = 1
    resource_weights: ResMap = _freeze({res.CPU: 1, res.MEMORY: 1})
    headroom: ResMap = ()  # resource -> max utilization percent; () = no mask

    def __post_init__(self):
        object.__setattr__(
            self, "resource_weights", _freeze(self.resource_weights)
        )
        object.__setattr__(self, "headroom", _freeze(self.headroom))

    def weights_arr(self) -> jnp.ndarray:
        return jnp.asarray(
            res.weights_vector(dict(self.resource_weights)), jnp.int64
        )

    def headroom_arr(self) -> jnp.ndarray:
        """Per-resource headroom percent; 0 = unconstrained dimension."""
        return jnp.asarray(
            res.weights_vector(dict(self.headroom)), jnp.int64
        )


@dataclasses.dataclass(frozen=True)
class CycleConfig:
    """One scheduling cycle's plugin set and weights.

    Plugin score weights mirror the k8s framework's per-plugin weight
    multiplier applied when summing plugin scores.
    """

    loadaware: LoadAwareArgs = LoadAwareArgs()
    fit_scoring_strategy: str = LEAST_ALLOCATED
    fit_resource_weights: ResMap = _freeze({res.CPU: 1, res.MEMORY: 1})
    fit_plugin_weight: int = 1
    loadaware_plugin_weight: int = 1
    enable_loadaware: bool = True
    enable_fit_score: bool = True
    # fused scoring terms (ISSUE 15; solver/terms.py registry): None =
    # term disabled.  Frozen hashable dataclasses — the configs are
    # static jit arguments, and the registry derives each term's score
    # upper bound from them (solver/topk.py score_upper_bound), so the
    # jit cache and the serving top-k path never key on data.
    heterogeneity: "HeterogeneityTermArgs | None" = None
    sensitivity: "SensitivityTermArgs | None" = None
    packing: "PackingTermArgs | None" = None
    # Sparse candidate-set scoring (ISSUE 16; solver/candidates.py).
    # ``candidate_width`` > 0 turns the sparse [P, C] serving path on:
    # each pod is scored only against its C-wide candidate list instead
    # of every node.  The width is a POWER OF TWO and rides the config
    # as a static jit argument — the candidate list is padded to C, so
    # C never crosses a jit boundary traced (the koordlint
    # retrace-hazard rule shape 6 rejects traced candidate counts).
    # 0 = dense engines only.  256 is the recommended serving width.
    candidate_width: int = 0
    # How many exact lazy merge-refreshes a candidate residency may
    # accumulate before the engine forces a full rebuild (refresh
    # reason "stale" on koord_scorer_candidate_refresh_total).  Bounds
    # merge-chain length so a long warm stream cannot degrade into an
    # unbounded sequence of incremental sorts.
    candidate_max_stale: int = 8

    def __post_init__(self):
        object.__setattr__(
            self, "fit_resource_weights", _freeze(self.fit_resource_weights)
        )
        cw = int(self.candidate_width)
        if cw < 0 or (cw & (cw - 1)) != 0:
            raise ValueError(
                "candidate_width must be 0 (sparse off) or a power of "
                f"two, got {self.candidate_width!r}"
            )
        if int(self.candidate_max_stale) < 1:
            raise ValueError(
                "candidate_max_stale must be >= 1, got "
                f"{self.candidate_max_stale!r}"
            )

    # Dense device-side encodings (constant-folded under jit)
    def loadaware_weights_arr(self) -> jnp.ndarray:
        return jnp.asarray(
            res.weights_vector(dict(self.loadaware.resource_weights)), jnp.int64
        )

    def loadaware_thresholds_arr(self) -> jnp.ndarray:
        """Filter thresholds: the aggregated profile's when configured
        (load_aware.go:157-162), else the plain usage thresholds."""
        agg = self.loadaware.aggregated
        if agg is not None and agg.usage_thresholds:
            src = agg.usage_thresholds
        else:
            src = self.loadaware.usage_thresholds
        return jnp.asarray(res.weights_vector(dict(src)), jnp.int64)

    def prod_thresholds_arr(self) -> jnp.ndarray:
        return jnp.asarray(
            res.weights_vector(dict(self.loadaware.prod_usage_thresholds)),
            jnp.int64,
        )

    def fit_weights_arr(self) -> jnp.ndarray:
        return jnp.asarray(
            res.weights_vector(dict(self.fit_resource_weights)), jnp.int64
        )


DEFAULT_CYCLE_CONFIG = CycleConfig()
