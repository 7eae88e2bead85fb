"""Batched assignment solver: one device program replaces the per-pod cycle.

The reference schedules one pod at a time: PreFilter -> parallel Filter ->
parallel Score -> Reserve mutates plugin caches (assign-cache
``plugins/loadaware/pod_assign_cache.go``; NodeInfo requested) so the next
pod sees the updated world.  ``greedy_assign`` reproduces those sequential
semantics exactly with a ``lax.scan`` over pods in queue order, carrying
(node_requested, node_estimated, quota_used) as scan state — so its
placements match the reference pod-for-pod — while ``score_cycle`` is the
stateless "score every pending pod at once" tensor program for score-only
parity and for the descheduler's candidate ranking.

Queue order follows the Coscheduling QueueSort (``coscheduling.go:118``):
higher priority first, then stable by submission index, with a gang's
members adjacent at the place of its first member (``queue_order``).

Strict Coscheduling (``constraints/gang.py``; PostFilter
``rejectGangGroupById`` and Unreserve, ``coscheduling/core/core.go``):
the carry holds a checkpoint of (node_requested, node_estimated,
quota_used) taken at each gang's first member.  A member that finds no
feasible node while its gang has fewer than minMember placed restores
the checkpoint, so later pods see the freed capacity, and the gang's
remaining members are skipped; ``strict_post_pass`` then gives every
member of a rejected gang assignment -1 (WAIT_GANG where it had been
placed).  Every gang is Strict: the wire carries no mode.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from koordinator_tpu.config import CycleConfig, DEFAULT_CYCLE_CONFIG, MOST_ALLOCATED
from koordinator_tpu.constraints.gang import (
    gang_opens,
    strict_gangs,
    strict_post_pass,
)
from koordinator_tpu.model.snapshot import ClusterSnapshot
from koordinator_tpu.obs import devprof
from koordinator_tpu.ops.fit import fit_mask, nonzero_requests
from koordinator_tpu.ops.loadaware import (
    loadaware_node_masks,
    select_score_usage,
)
from koordinator_tpu.model.snapshot import PriorityClass
from koordinator_tpu.ops.scoring import (
    least_requested_score,
    most_requested_score,
    weighted_resource_score,
)

STATUS_ASSIGNED = 0
STATUS_UNSCHEDULABLE = 1
STATUS_WAIT_GANG = 2


@dataclasses.dataclass
class CycleResult:
    assignment: jnp.ndarray  # i32[P] node index, -1 = none
    status: jnp.ndarray  # i32[P]
    scores: Optional[jnp.ndarray] = None  # i64[P, N] (score_cycle only)
    node_requested: Optional[jnp.ndarray] = None  # i64[N, R] post-cycle
    node_estimated: Optional[jnp.ndarray] = None  # i64[N, R] post-cycle
    quota_used: Optional[jnp.ndarray] = None  # i64[Q, R] post-cycle
    # sequential steps the device ran: the multi-chip wave path's
    # (parallel/shard_assign.py) rounds, ~P/wave-prefix vs P scan steps;
    # the dense kernel's pod steps, one per valid pod (its padding rows
    # are not run); None on the scan
    rounds: Optional[jnp.ndarray] = None
    # which code path produced the result ("pallas" single-kernel cycle,
    # "scan" lax.scan, "shard" multi-chip shard_map) — static metadata so callers (bridge AssignReply, bench)
    # can surface degraded-path runs; VERDICT r2 flagged the
    # silent-fallback invisibility
    path: Optional[str] = None


jax.tree_util.register_dataclass(
    CycleResult,
    data_fields=[
        "assignment",
        "status",
        "scores",
        "node_requested",
        "node_estimated",
        "quota_used",
        "rounds",
    ],
    meta_fields=["path"],
)


def queue_order(
    priority: jnp.ndarray,
    valid: jnp.ndarray,
    gang: Optional[jnp.ndarray] = None,
    num_gangs: int = 0,
) -> jnp.ndarray:
    """Pod visit order: priority desc, stable by index; padding last.

    With ``gang`` (i32[P], -1 = none, else below ``num_gangs``; from
    ``constraints.gang.strict_gangs``) every member of gang g takes the
    key (-priority of g's first member, g's first row, row), "first"
    being the member that comes first in the order above, so a gang's
    members are adjacent at its first member's place; a pod with no gang
    keeps (-priority, row, row).  Where a gang's members already sit
    together in the order above (one priority and adjacent rows, as one
    job's workers are synced) the order is unchanged, and it is
    Coscheduling's QueueSort (priority, then the PodGroup's creation,
    then its id).  A mixed-priority gang's later members depart from
    Koordinator's per-pod priority to travel with their first member."""
    key = jnp.where(valid, -priority.astype(jnp.int64), jnp.iinfo(jnp.int64).max)
    order = jnp.argsort(key, stable=True)
    if gang is None:
        return order
    P = priority.shape[0]
    rank = jnp.zeros((P,), jnp.int64).at[order].set(jnp.arange(P, dtype=jnp.int64))
    slot = jnp.where(gang >= 0, gang, num_gangs)
    first = jnp.full((num_gangs + 1,), P, jnp.int64).at[slot].min(rank)
    lead = jnp.where(gang >= 0, first[slot], rank)
    return jnp.argsort(lead * P + jnp.arange(P, dtype=jnp.int64))


def _fit_score_requests(requests: jnp.ndarray) -> jnp.ndarray:
    return nonzero_requests(requests)


def _combined_scores(
    snapshot: ClusterSnapshot,
    node_requested: jnp.ndarray,
    node_estimated: jnp.ndarray,
    cfg: CycleConfig,
    pod_requests: jnp.ndarray,
    pod_score_requests: jnp.ndarray,
    pod_estimated: jnp.ndarray,
):
    """Weighted sum of enabled plugin scores; broadcasting over [P?, N]."""
    nodes = snapshot.nodes
    total = jnp.zeros(
        pod_requests.shape[:-1] + (nodes.allocatable.shape[0],), jnp.int64
    )
    if cfg.enable_fit_score:
        t = node_requested + pod_score_requests[..., None, :]
        if cfg.fit_scoring_strategy == MOST_ALLOCATED:
            per_res = most_requested_score(t, nodes.allocatable)
        else:
            per_res = least_requested_score(t, nodes.allocatable)
        total = total + cfg.fit_plugin_weight * weighted_resource_score(
            per_res, cfg.fit_weights_arr()
        )
    if cfg.enable_loadaware:
        usage_np, usage_prod = select_score_usage(nodes, cfg)
        usage_sel = usage_np[None, :, :]
        if usage_prod is not None:
            is_prod = (
                snapshot.pods.priority_class == int(PriorityClass.PROD)
            )
            usage_sel = jnp.where(
                is_prod[:, None, None], usage_prod[None, :, :], usage_sel
            )
        est_used = usage_sel + node_estimated + pod_estimated[..., None, :]
        per_res = least_requested_score(est_used, nodes.allocatable)
        la = weighted_resource_score(per_res, cfg.loadaware_weights_arr())
        la = jnp.where(nodes.metric_fresh, la, 0)
        total = total + cfg.loadaware_plugin_weight * la
    return total


def step_feasible_scores(
    node_requested: jnp.ndarray,  # i64[N, R] carried
    node_estimated: jnp.ndarray,  # i64[N, R] carried
    quota_used: jnp.ndarray,  # i64[Q, R] carried
    alloc: jnp.ndarray,  # i64[N, R]
    usage: jnp.ndarray,  # i64[N, R]
    fresh: jnp.ndarray,  # bool[N]
    node_ok: jnp.ndarray,  # bool[N] valid & loadaware filter
    req: jnp.ndarray,  # i64[R] one pod
    sreq: jnp.ndarray,  # i64[R]
    est: jnp.ndarray,  # i64[R]
    qid: jnp.ndarray,  # i32 scalar
    is_valid: jnp.ndarray,  # bool scalar
    qrt: jnp.ndarray,  # i64[Q, R]
    qlim: jnp.ndarray,  # bool[Q, R]
    cfg: CycleConfig,
):
    """One pod's Filter+Score against a node-state block -> (feasible[N],
    scores[N]).  The single source of the sequential-cycle step semantics,
    shared by ``greedy_assign`` and the shard_map variant
    (parallel/shard_assign.py); the Pallas kernel mirrors it in i32."""
    q = jnp.maximum(qid, 0)
    need = req > 0
    fits = jnp.all(
        jnp.where(need[None, :], node_requested + req[None, :] <= alloc, True),
        axis=-1,
    )
    quota_ok = jnp.where(
        qid >= 0,
        jnp.all(jnp.where(qlim[q], quota_used[q] + req <= qrt[q], True)),
        True,
    )
    feasible = fits & node_ok & quota_ok & is_valid

    total = jnp.zeros((alloc.shape[0],), jnp.int64)
    if cfg.enable_fit_score:
        t = node_requested + sreq[None, :]
        if cfg.fit_scoring_strategy == MOST_ALLOCATED:
            per_res = most_requested_score(t, alloc)
        else:
            per_res = least_requested_score(t, alloc)
        total = total + cfg.fit_plugin_weight * weighted_resource_score(
            per_res, cfg.fit_weights_arr()
        )
    if cfg.enable_loadaware:
        est_used = usage + node_estimated + est[None, :]
        per_res = least_requested_score(est_used, alloc)
        la = jnp.where(fresh, weighted_resource_score(per_res, cfg.loadaware_weights_arr()), 0)
        total = total + cfg.loadaware_plugin_weight * la
    return feasible, total


def feasibility_mask(
    snapshot: ClusterSnapshot, cfg: CycleConfig = DEFAULT_CYCLE_CONFIG
) -> jnp.ndarray:
    """The MASK half of :func:`score_all`, standalone (ISSUE 16).

    Requests-fit + node-validity + loadaware freshness/threshold masks +
    every enabled term's feasibility mask, with zero scoring arithmetic
    — the cheap feasibility pre-mask the sparse candidate engine
    (solver/candidates.py) evaluates blockwise to pick each pod's
    candidate set without ever materializing the [P, N] score tensor.
    Cellwise in (pod row, node row) like everything else in the body,
    so it is shape-polymorphic over gathered sub-snapshots.

    Exactness: ``score_all`` composes this mask with the score half;
    masks only AND together and scores only add, so factoring changes
    no bits — the bool this returns at (p, n) is the very ``feasible``
    bit a full ``score_cycle`` would produce.
    """
    pods, nodes = snapshot.pods, snapshot.nodes
    feasible = fit_mask(
        pods.requests, nodes.requested, nodes.allocatable, nodes.valid, pods.valid
    )
    if cfg.enable_loadaware:
        mask_default, mask_prod = loadaware_node_masks(nodes, cfg)
        is_prod = pods.priority_class == int(PriorityClass.PROD)
        la_mask = jnp.where(
            is_prod[:, None], mask_prod[None, :], mask_default[None, :]
        )
        feasible = feasible & la_mask
    from koordinator_tpu.solver.terms import apply_term_masks

    return apply_term_masks(snapshot, cfg, feasible)


def score_all(snapshot: ClusterSnapshot, cfg: CycleConfig = DEFAULT_CYCLE_CONFIG):
    """The scoring math of :func:`score_cycle`, un-jitted.

    The ONE statement of the stateless Filter+Score semantics, shared by
    the jitted full rescore (``score_cycle``) and the incremental
    column/row rescore (solver/incremental.py, ISSUE 9) — every term is
    cellwise in (pod row, node row), which is exactly what makes
    "gather rows, score, scatter back" bit-identical to a full rescore,
    and sharing the body is what keeps the two engines from drifting.

    The fused scoring-term registry (ISSUE 15, solver/terms.py) rides
    the same body: heterogeneity / sensitivity / packing contributions
    are added INSIDE this one tensor program — cellwise by contract, so
    the incremental exactness argument extends to them unchanged and a
    three-term Score still costs exactly one launch.

    Composed (ISSUE 16) from :func:`feasibility_mask` (the mask half —
    the sparse engine's standalone pre-mask) and the score half; the
    halves commute, so the factoring is bitwise free.
    """
    pods, nodes = snapshot.pods, snapshot.nodes
    feasible = feasibility_mask(snapshot, cfg)
    zero_nr = jnp.zeros_like(nodes.requested)
    scores = _combined_scores(
        snapshot,
        nodes.requested,
        zero_nr,
        cfg,
        pods.requests,
        _fit_score_requests(pods.requests),
        pods.estimated,
    )
    from koordinator_tpu.solver.terms import apply_term_scores

    return apply_term_scores(snapshot, cfg, scores), feasible


@devprof.boundary("solver.greedy.score_cycle")
@partial(jax.jit, static_argnames=("cfg",))
def score_cycle(snapshot: ClusterSnapshot, cfg: CycleConfig = DEFAULT_CYCLE_CONFIG):
    """Stateless batch scoring: scores + feasibility for every (pod, node).

    Equivalent to running the reference's Filter+Score for each pending pod
    against the *initial* snapshot (no intra-batch Reserve effects).
    Returns (scores i64[P, N], feasible bool[P, N]).
    """
    return score_all(snapshot, cfg)


@devprof.boundary("solver.greedy.greedy_assign")
@partial(jax.jit, static_argnames=("cfg",))
def greedy_assign(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    extra_mask: Optional[jnp.ndarray] = None,  # bool[P, N] extended-plugin Filter
    extra_scores: Optional[jnp.ndarray] = None,  # i64[P, N] extended-plugin Score
) -> CycleResult:
    """Sequential-parity greedy assignment of the whole pending batch.

    ``extra_mask``/``extra_scores`` carry the extended plugins' (NUMA,
    reservation, device-share) stateless Filter/Score tensors into the
    sequential scan; their intra-batch allocation state is settled exactly
    at Reserve on the host (scheduler.framework), like the reference's
    Reserve phase caches.
    """
    pods, nodes, gangs, quotas = (
        snapshot.pods,
        snapshot.nodes,
        snapshot.gangs,
        snapshot.quotas,
    )
    P = pods.capacity
    N = nodes.allocatable.shape[0]

    G = gangs.min_member.shape[0]
    gang, valid, need = strict_gangs(pods.gang_id, pods.valid, gangs.min_member)
    order = queue_order(pods.priority, valid, gang, G)
    opens = gang_opens(gang[order])
    score_requests = _fit_score_requests(pods.requests)

    mask_default, mask_prod = loadaware_node_masks(nodes, cfg)
    if not cfg.enable_loadaware:
        mask_default = jnp.ones_like(mask_default)
        mask_prod = mask_default
    node_ok_default = nodes.valid & mask_default
    node_ok_prod = nodes.valid & mask_prod
    usage_np, usage_prod = select_score_usage(nodes, cfg)
    prod_sensitive = cfg.enable_loadaware and (
        usage_prod is not None
        or bool(dict(cfg.loadaware.prod_usage_thresholds))
    )

    def step(state, x):
        p, opens_p = x
        cur, ck, placed, rejected = state
        # Strict gangs: checkpoint at the gang's first member; a
        # rejected gang's remaining members are skipped
        ck = jax.tree_util.tree_map(
            lambda c, v: jnp.where(opens_p, v, c), ck, cur
        )
        placed = jnp.where(opens_p, 0, placed)
        rejected = jnp.where(opens_p, False, rejected)
        in_gang = need[p] > 0
        is_valid = valid[p] & ~(in_gang & rejected)
        node_requested, node_estimated, quota_used = cur
        req = pods.requests[p]
        est = pods.estimated[p]
        qid = pods.quota_id[p]
        q = jnp.maximum(qid, 0)
        if prod_sensitive:
            is_prod_p = pods.priority_class[p] == int(PriorityClass.PROD)
            node_ok_p = jnp.where(is_prod_p, node_ok_prod, node_ok_default)
            usage_p = (
                jnp.where(is_prod_p, usage_prod, usage_np)
                if usage_prod is not None
                else usage_np
            )
        else:
            node_ok_p = node_ok_default
            usage_p = usage_np

        feasible, scores = step_feasible_scores(
            node_requested,
            node_estimated,
            quota_used,
            nodes.allocatable,
            usage_p,
            nodes.metric_fresh,
            node_ok_p,
            req,
            score_requests[p],
            est,
            qid,
            is_valid,
            quotas.runtime,
            quotas.limited,
            cfg,
        )
        if extra_mask is not None:
            feasible = feasible & extra_mask[p]
        if extra_scores is not None:
            scores = scores + extra_scores[p]
        masked = jnp.where(feasible, scores, jnp.iinfo(jnp.int64).min)
        best = jnp.argmax(masked).astype(jnp.int32)
        any_feasible = jnp.any(feasible)
        chosen = jnp.where(any_feasible, best, -1)

        assign_onehot = (jnp.arange(N) == chosen) & any_feasible
        node_requested = node_requested + jnp.where(
            assign_onehot[:, None], req[None, :], 0
        )
        node_estimated = node_estimated + jnp.where(
            assign_onehot[:, None], est[None, :], 0
        )
        quota_used = jnp.where(
            any_feasible & (qid >= 0),
            quota_used.at[q].add(req),
            quota_used,
        )
        cur = (node_requested, node_estimated, quota_used)
        # a member with no node before its gang reached minMember
        # rejects the gang: Unreserve every placement it made
        fail = in_gang & is_valid & ~any_feasible & (placed < need[p])
        cur = jax.tree_util.tree_map(
            lambda c, v: jnp.where(fail, c, v), ck, cur
        )
        placed = placed + (in_gang & any_feasible).astype(jnp.int32)
        return (cur, ck, placed, rejected | fail), chosen

    start = (nodes.requested, jnp.zeros_like(nodes.requested), quotas.used)
    init = (start, start, jnp.int32(0), jnp.bool_(False))
    ((node_requested, node_estimated, quota_used), *_), chosen_in_order = (
        lax.scan(step, init, (order, opens))
    )

    chosen = jnp.full((P,), -1, jnp.int32).at[order].set(chosen_in_order)
    assignment, status = strict_post_pass(chosen, gang, need, G)

    return CycleResult(
        assignment=assignment,
        status=status,
        node_requested=node_requested,
        node_estimated=node_estimated,
        quota_used=quota_used,
        path="scan",
    )
