"""Dense-layout Pallas cycle kernel: nodes on lanes, resources on sublanes.

The first-generation kernel (solver/pallas_cycle.py) puts RESOURCES on the
128-lane axis, so every per-pod vector op touches [N, 128] i32 tiles (256
vregs at 2k nodes) while only ~13 lanes carry data — measured ~12us/pod on
v5e, entirely VPU-occupancy-bound on padding.  This kernel transposes the
whole state to ``[RP=16, N]`` — resources (13) plus three node-flag rows on
the SUBLANE axis, nodes riding the lane axis — so the same math touches 32
vregs instead of 256:

* per-pod column extraction (requests / estimates / quota row) is a one-hot
  lane reduction ([16, 128] ops — 2 vregs);
* Filter violations reduce over the 16 sublanes to a [1, N] row;
* argmax over nodes is a native lane reduction of a [1, N] row with the
  same first-index tie-break (min over matching lane iota);
* Reserve commits are full-tensor one-hot-lane adds on [16, N].

Semantics are bit-identical with solver/greedy.py's lax.scan (the parity
oracle mirroring the reference's sequential cycle,
``pkg/scheduler/frameworkext/framework_extender.go:192,216``); the same
i32-soundness contract as the wide kernel applies (model/resources.py MiB
units; dispatcher gates via pallas_inputs_fit_i32).

Strict Coscheduling gangs (``constraints/gang.py``) compile a variant of
the kernel (``has_gangs``, static; a snapshot with no gang member runs
the kernel without it).  Its fourth scalar-prefetch operand holds each
pod's gang word, 0 for a pod with no gang, else ``min_member << 1 |
first``.  A gang-free pod runs the plain step behind a scalar gate.  A
gang member runs the step with the gang's state, carried across grid
steps in scratch: at its gang's first member the kernel checkpoints
(node requested, assigned estimates, quota used) in VMEM and clears the
gang's placed count and rejected flag; a rejected gang's member is
skipped; a member with no feasible node while fewer than min member are
placed restores the checkpoint and rejects its gang.  The rows of the
members placed before are already written, so the XLA post-pass
(``strict_post_pass``) gives every member of a rejected gang -1.

The kernel runs the rows of the valid pods alone.  Their count, a
scalar-prefetch operand (data: a backlog of another size runs the same
compiled kernel), bounds the last block's row loop, and a grid step
past it does no vector work, so every row run is a valid pod and the
kernel takes no per-row valid flag; every step first fills its ``chosen``
block with -1, so a row not run reads as an infeasible one.  This rests
on ``queue_order`` putting every valid pod before every invalid one.
The count is the result's ``rounds``: the pod steps run.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from koordinator_tpu.config import CycleConfig, DEFAULT_CYCLE_CONFIG, MOST_ALLOCATED
from koordinator_tpu.constraints.gang import (
    gang_opens,
    has_gang_members,
    strict_gangs,
    strict_post_pass,
)
from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import MAX_NODE_SCORE, ClusterSnapshot
from koordinator_tpu.obs import devprof
from koordinator_tpu.model.snapshot import PriorityClass
from koordinator_tpu.ops.fit import nonzero_requests
from koordinator_tpu.ops.loadaware import (
    loadaware_node_masks,
    select_score_usage,
)
from koordinator_tpu.solver.greedy import (
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    CycleResult,
    queue_order,
)
from koordinator_tpu.solver.pallas_cycle import (
    I32_MIN,
    LANES,
    XCOMB_INFEASIBLE,
    _i32,
)

# sublane rows: resources occupy [0, NUM_RESOURCES); flags ride the spare
# rows of the usage tensor (their weight rows are zero, their request rows
# are zero, so they can never contribute to a score or a Filter violation)
RP = 16
FLAG_ROW_OK = RP - 3  # valid & loadaware default mask
FLAG_ROW_FRESH = RP - 2  # metric_fresh
FLAG_ROW_PROD_OK = RP - 1  # valid & prod-threshold mask
assert res.NUM_RESOURCES <= FLAG_ROW_OK, (
    "resource axis grew into the dense kernel's flag rows; bump RP"
)


def _exact_div(v, safe, recip):
    """Exact nonnegative i32 floor division via f32 reciprocal (see
    pallas_cycle._exact_div for the ablation and soundness argument)."""
    q = (v.astype(jnp.float32) * recip).astype(jnp.int32)
    r = v - q * safe
    q = q + jnp.where(r >= safe, _i32(1), _i32(0))
    q = q - jnp.where(v - q * safe < _i32(0), _i32(1), _i32(0))
    return q


def _least_requested(t, cap, recip):
    safe = jnp.maximum(cap, _i32(1))
    free = jnp.maximum(cap - t, _i32(0))
    score = _exact_div(free * _i32(MAX_NODE_SCORE), safe, recip)
    return jnp.where((cap == _i32(0)) | (t > cap), _i32(0), score)


def _most_requested(t, cap, recip):
    safe = jnp.maximum(cap, _i32(1))
    clamped = jnp.minimum(t, cap)
    score = _exact_div(clamped * _i32(MAX_NODE_SCORE), safe, recip)
    return jnp.where(cap == _i32(0), _i32(0), score)


def _weighted_rows(per_res, w_col, w_sum: int):
    """[RP, N] per-resource scores x [RP, 1] weights -> [1, N]."""
    if w_sum == 0:
        return jnp.zeros((1, per_res.shape[1]), jnp.int32)
    s = jnp.sum(per_res * w_col, axis=0, keepdims=True, dtype=jnp.int32)
    return _exact_div(s, _i32(w_sum), np.float32(1.0 / w_sum))


def _onehot_col(tile, j, width):
    """Extract lane column ``j`` of ``tile`` [RP, width] -> [RP, 1] via a
    masked lane reduction (dynamic lane slicing is costly on the VPU)."""
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1) == j
    return jnp.sum(
        jnp.where(lane, tile, _i32(0)), axis=1, keepdims=True, dtype=jnp.int32
    )


def _cycle_kernel_dense(
    # scalar prefetch (SMEM)
    qid_ref,  # i32[P]
    pprod_ref,  # i32[P]
    nvalid_ref,  # i32[1]: the valid pods, rows [0, n_valid) of the order
    *refs,  # with has_gangs the gang words i32[P] (SMEM) first; then
    # inputs (VMEM) — all [RP, *] with nodes/pods/quotas on lanes
    #   preq, psreq, pest i32[RP, B]; alloc, req0 (initial node
    #   requested), usage (flag rows OK/FRESH/PROD_OK) i32[RP, N];
    #   qrt, qlim, quse0 i32[RP, Qp]; w i32[RP, 128] (col 0 fit weights,
    #   col 1 loadaware weights); optional uprod i32[RP, N]; optional
    #   xcomb i32[B, N]; then outputs (chosen, nreq, nest, quse); then,
    #   with has_gangs, scratch (ck_nreq, ck_nest, ck_quse, gstate)
    block: int,
    cfg: CycleConfig,
    has_extras: bool,
    has_prod: bool,
    has_gangs: bool,
):
    if has_gangs:
        pgang_ref, refs = refs[0], refs[1:]
    (preq_ref, psreq_ref, pest_ref, alloc_ref, req0_ref, usage_ref, qrt_ref,
     qlim_ref, quse0_ref, w_ref) = refs[:10]
    rest = refs[10:]
    if has_gangs:
        rest, (ck_nreq_ref, ck_nest_ref, ck_quse_ref, gstate_ref) = (
            rest[:-4], rest[-4:]
        )
    if has_prod:
        uprod_ref = rest[0]
        rest = rest[1:]
    else:
        uprod_ref = None
    if has_extras:
        xcomb_ref = rest[0]
        rest = rest[1:]
    else:
        xcomb_ref = None
    (chosen_ref, nreq_ref, nest_ref, quse_ref) = rest

    i = pl.program_id(0)

    @pl.when(i == _i32(0))
    def _init():
        nreq_ref[:] = req0_ref[:]
        nest_ref[:] = jnp.zeros_like(nest_ref)
        quse_ref[:] = quse0_ref[:]

    # a row not run reads as an infeasible one; queue_order puts every
    # valid pod before the padding, so rows [0, n_valid) are all run
    chosen_ref[:] = jnp.full(chosen_ref.shape, -1, jnp.int32)
    first = i * block
    n_valid = nvalid_ref[0]
    fit_w_sum = sum(res.weights_vector(dict(cfg.fit_resource_weights)))
    la_w_sum = sum(res.weights_vector(dict(cfg.loadaware.resource_weights)))

    @pl.when(first < n_valid)
    def _valid_rows():
        alloc = alloc_ref[:]
        n_lanes = alloc.shape[1]
        q_lanes = quse0_ref.shape[1]
        node_ok = usage_ref[FLAG_ROW_OK : FLAG_ROW_OK + 1, :] != _i32(0)
        fresh = usage_ref[FLAG_ROW_FRESH : FLAG_ROW_FRESH + 1, :] != _i32(0)
        lane_iota = lax.broadcasted_iota(jnp.int32, (1, n_lanes), 1)
        qlane_iota = lax.broadcasted_iota(jnp.int32, (1, q_lanes), 1)

        fit_w_col = w_ref[:, 0:1]
        la_w_col = w_ref[:, 1:2]
        recip = 1.0 / jnp.maximum(alloc, _i32(1)).astype(jnp.float32)

        def pod_step(j, p, gang_word=None):
            """One pod's Filter, Score and Reserve; ``gang_word`` (a scalar,
            nonzero) runs it as a member of a Strict gang."""
            req = _onehot_col(preq_ref[:], j, block)  # [RP, 1]
            sreq = _onehot_col(psreq_ref[:], j, block)
            est = _onehot_col(pest_ref[:], j, block)
            qid = qid_ref[p]
            qidx = jnp.maximum(qid, _i32(0))
            if gang_word is not None:

                @pl.when((gang_word & _i32(1)) != _i32(0))
                def _checkpoint():
                    ck_nreq_ref[:] = nreq_ref[:]
                    ck_nest_ref[:] = nest_ref[:]
                    ck_quse_ref[:] = quse_ref[:]
                    gstate_ref[:] = jnp.zeros_like(gstate_ref)

                placed = gstate_ref[0:1, 0:1]  # [1, 1] members placed
                # a rejected gang's remaining members are skipped
                live = gstate_ref[1:2, 0:1] == _i32(0)
            if has_prod:
                is_prod = pprod_ref[p] != _i32(0)
                node_ok_p = (
                    jnp.where(
                        is_prod,
                        usage_ref[FLAG_ROW_PROD_OK : FLAG_ROW_PROD_OK + 1, :],
                        usage_ref[FLAG_ROW_OK : FLAG_ROW_OK + 1, :],
                    )
                    != _i32(0)
                )
                usage_p = jnp.where(is_prod, uprod_ref[:], usage_ref[:])
            else:
                node_ok_p = node_ok
                usage_p = usage_ref[:]

            nreq = nreq_ref[:]
            # Filter: Fit (only requested resources constrain) + node flags
            need = req > _i32(0)  # [RP, 1] broadcasts over lanes
            fviol = jnp.where(need & (nreq + req > alloc), _i32(1), _i32(0))
            fits = jnp.max(fviol, axis=0, keepdims=True) == _i32(0)  # [1, N]
            # ElasticQuota admission on limited dimensions
            qlane = qlane_iota == qidx
            quse_col = jnp.sum(
                jnp.where(qlane, quse_ref[:], _i32(0)),
                axis=1,
                keepdims=True,
                dtype=jnp.int32,
            )
            qrt_col = jnp.sum(
                jnp.where(qlane, qrt_ref[:], _i32(0)),
                axis=1,
                keepdims=True,
                dtype=jnp.int32,
            )
            qlim_col = jnp.sum(
                jnp.where(qlane, qlim_ref[:], _i32(0)),
                axis=1,
                keepdims=True,
                dtype=jnp.int32,
            )
            qviol = jnp.where(
                (qlim_col != _i32(0)) & (quse_col + req > qrt_col),
                _i32(1),
                _i32(0),
            )
            qok = jnp.max(qviol) == _i32(0)
            feasible = fits & node_ok_p & ((qid < _i32(0)) | qok)
            if gang_word is not None:
                feasible = feasible & live
            if has_extras:
                xv = xcomb_ref[pl.ds(j, 1), :]  # [1, N]
                feasible = feasible & (xv != _i32(XCOMB_INFEASIBLE))

            # Score: NodeResourcesFit + LoadAware, exact integer math
            total = jnp.zeros((1, n_lanes), jnp.int32)
            if cfg.enable_fit_score:
                t = nreq + sreq
                if cfg.fit_scoring_strategy == MOST_ALLOCATED:
                    per_res = _most_requested(t, alloc, recip)
                else:
                    per_res = _least_requested(t, alloc, recip)
                total = total + _i32(cfg.fit_plugin_weight) * _weighted_rows(
                    per_res, fit_w_col, fit_w_sum
                )
            if cfg.enable_loadaware:
                est_used = usage_p + nest_ref[:] + est
                per_res = _least_requested(est_used, alloc, recip)
                la = _weighted_rows(per_res, la_w_col, la_w_sum)
                total = total + _i32(cfg.loadaware_plugin_weight) * jnp.where(
                    fresh, la, _i32(0)
                )
            if has_extras:
                total = total + jnp.where(
                    xv == _i32(XCOMB_INFEASIBLE), _i32(0), xv
                )

            masked = jnp.where(feasible, total, I32_MIN)
            best = jnp.max(masked)
            any_feasible = best > I32_MIN
            chosen = jnp.min(
                jnp.where(masked == best, lane_iota, _i32(n_lanes))
            )
            chosen = jnp.where(any_feasible, chosen, _i32(-1))

            # Reserve: one-hot-lane adds on the [RP, N] state
            commit_lane = (lane_iota == chosen) & any_feasible  # [1, N]
            nreq_new = nreq + jnp.where(commit_lane, req, _i32(0))
            nest_new = nest_ref[:] + jnp.where(commit_lane, est, _i32(0))
            quse_commit = qlane & any_feasible & (qid >= _i32(0))
            quse_new = quse_ref[:] + jnp.where(quse_commit, req, _i32(0))
            if gang_word is not None:
                # no node before the gang reached min member: Unreserve
                # every placement it made, reject it
                placed_now = jnp.where(any_feasible, _i32(1), _i32(0))
                fail = jnp.where(
                    live & (placed_now == _i32(0))
                    & (placed < (gang_word >> _i32(1))),
                    _i32(1),
                    _i32(0),
                )  # [1, 1]
                # Mosaic broadcasts along one axis at a time: lanes first
                fail_n = jnp.broadcast_to(fail, (1, n_lanes)) != _i32(0)
                fail_q = jnp.broadcast_to(fail, (1, q_lanes)) != _i32(0)
                nreq_new = jnp.where(fail_n, ck_nreq_ref[:], nreq_new)
                nest_new = jnp.where(fail_n, ck_nest_ref[:], nest_new)
                quse_new = jnp.where(fail_q, ck_quse_ref[:], quse_new)
                gstate_ref[0:1, :] = jnp.broadcast_to(
                    placed + placed_now, (1, LANES)
                )
                gstate_ref[1:2, :] = jnp.broadcast_to(
                    gstate_ref[1:2, 0:1] | fail, (1, LANES)
                )
            nreq_ref[:] = nreq_new
            nest_ref[:] = nest_new
            quse_ref[:] = quse_new

            chosen_ref[pl.ds(j, 1), :] = jnp.full(
                (1, LANES), chosen, jnp.int32
            )

        def step(j, _):
            p = first + j
            if not has_gangs:
                pod_step(j, p)
                return jnp.int32(0)
            # the scalar gate: a gang-free pod runs the plain step
            gang_word = pgang_ref[p]

            @pl.when(gang_word == _i32(0))
            def _plain():
                pod_step(j, p)

            @pl.when(gang_word != _i32(0))
            def _member():
                pod_step(j, p, gang_word)

            return jnp.int32(0)

        lax.fori_loop(
            jnp.int32(0), jnp.minimum(n_valid - first, _i32(block)), step,
            jnp.int32(0),
        )


@devprof.boundary("solver.pallas_dense._run_cycle_dense")
@partial(jax.jit, static_argnames=("cfg", "block", "interpret"))
def _run_cycle_dense(
    preq, psreq, pest, qid, pprod, nvalid, alloc, req0, usage, qrt,
    qlim, quse0, weights, uprod=None, xcomb=None, pgang=None, *,
    cfg: CycleConfig, block: int, interpret: bool
):
    P = preq.shape[1]
    N = alloc.shape[1]
    Qp = qrt.shape[1]
    has_extras = xcomb is not None
    has_prod = uprod is not None
    has_gangs = pgang is not None
    grid = (P // block,)
    _z = np.int32(0)
    node_spec = pl.BlockSpec(
        (RP, N), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM
    )
    quota_spec = pl.BlockSpec(
        (RP, Qp), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM
    )
    pod_spec = pl.BlockSpec(
        (RP, block), lambda i, *_: (_z, i), memory_space=pltpu.VMEM
    )
    in_specs = (
        [pod_spec] * 3
        + [node_spec] * 3
        + [quota_spec] * 3
        + [
            pl.BlockSpec(
                (RP, LANES), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM
            )
        ]
    )
    operands = [preq, psreq, pest, alloc, req0, usage, qrt, qlim, quse0, weights]
    if has_prod:
        in_specs += [node_spec]
        operands += [uprod]
    if has_extras:
        # [P, N] with nodes on lanes: each grid step streams a (block, N)
        # tile; the per-pod row is a cheap dynamic sublane slice
        in_specs += [
            pl.BlockSpec((block, N), lambda i, *_: (i, _z), memory_space=pltpu.VMEM)
        ]
        operands += [xcomb]
    scalars = [qid, pprod, nvalid]
    scratch = []
    if has_gangs:
        scalars += [pgang]
        # the open gang's checkpoint and its (placed, rejected) words
        scratch = [
            pltpu.VMEM((RP, N), jnp.int32),
            pltpu.VMEM((RP, N), jnp.int32),
            pltpu.VMEM((RP, Qp), jnp.int32),
            pltpu.VMEM((8, LANES), jnp.int32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (block, LANES), lambda i, *_: (i, _z), memory_space=pltpu.VMEM
            ),
            node_spec,
            node_spec,
            quota_spec,
        ],
        scratch_shapes=scratch,
    )
    kernel = partial(
        _cycle_kernel_dense,
        block=block,
        cfg=cfg,
        has_extras=has_extras,
        has_prod=has_prod,
        has_gangs=has_gangs,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, LANES), jnp.int32),
            jax.ShapeDtypeStruct((RP, N), jnp.int32),
            jax.ShapeDtypeStruct((RP, N), jnp.int32),
            jax.ShapeDtypeStruct((RP, Qp), jnp.int32),
        ],
        interpret=interpret,
    )(*scalars, *operands)


def _rows(a: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """[M, R] -> [RP, lanes] i32: transpose, resources on sublanes."""
    t = a.astype(jnp.int32).T
    return jnp.pad(t, ((0, RP - t.shape[0]), (0, lanes - t.shape[1])))


def greedy_assign_dense(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    interpret: bool = False,
    extra_mask=None,  # bool[P, N] extended-plugin Filter tensor
    extra_scores=None,  # i64[P, N] extended-plugin Score tensor
    has_gangs=None,
) -> CycleResult:
    """Dense-layout drop-in for greedy_assign on TPU (path="pallas").

    Same i32-headroom guard as the wide kernel: extended scores must stay
    under 2^29 so the accumulation cannot wrap.

    ``has_gangs``: whether any valid pod is a gang member, which selects
    the kernel's Strict-gang variant; callers that know it from host
    mirrors pass it, None reads it off the device.
    """
    if extra_scores is not None:
        peak = int(jnp.max(jnp.abs(extra_scores)))
        if peak >= 2**29:
            raise ValueError(
                f"extra_scores magnitude {peak} >= 2^29: out of the Pallas "
                "kernel's i32 headroom; use the lax.scan path (greedy_assign)"
            )
    if has_gangs is None:
        has_gangs = has_gang_members(
            snapshot.pods.gang_id, snapshot.pods.valid,
            snapshot.gangs.min_member,
        )
    return _greedy_assign_dense(
        snapshot, cfg, interpret, extra_mask, extra_scores, bool(has_gangs)
    )


@devprof.boundary("solver.pallas_dense._greedy_assign_dense")
@partial(jax.jit, static_argnames=("cfg", "interpret", "has_gangs"))
def _greedy_assign_dense(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    interpret: bool = False,
    extra_mask=None,
    extra_scores=None,
    has_gangs: bool = False,
) -> CycleResult:
    pods, nodes, gangs, quotas = (
        snapshot.pods,
        snapshot.nodes,
        snapshot.gangs,
        snapshot.quotas,
    )
    P = pods.capacity
    N = nodes.allocatable.shape[0]

    P_pad = -(-P // 128) * 128
    block = 128
    N_pad = -(-N // LANES) * LANES  # nodes ride the lane axis now
    valid = pods.valid
    pgang = None
    if has_gangs:
        G = gangs.min_member.shape[0]
        gang, valid, need = strict_gangs(pods.gang_id, valid, gangs.min_member)
        order = queue_order(pods.priority, valid, gang, G)
        word = jnp.where(
            gang[order] >= 0,
            need[order] * 2 + gang_opens(gang[order]).astype(jnp.int32),
            0,
        )
        pgang = jnp.pad(word.astype(jnp.int32), (0, P_pad - P))
    else:
        order = queue_order(pods.priority, valid)

    def _pods(a):
        return _rows(a[order], P_pad)

    preq = _pods(pods.requests)
    psreq = _pods(nonzero_requests(pods.requests))
    pest = _pods(pods.estimated)
    qid = jnp.pad(pods.quota_id[order].astype(jnp.int32), (0, P_pad - P))
    # the kernel runs the rows of the valid pods alone: data, so a
    # backlog of another size runs the same compiled kernel
    n_valid = jnp.sum(valid, dtype=jnp.int32)

    mask_default, mask_prod = loadaware_node_masks(nodes, cfg)
    if not cfg.enable_loadaware:
        mask_default = jnp.ones_like(mask_default)
        mask_prod = mask_default
    usage_np, usage_prod = select_score_usage(nodes, cfg)
    prod_sensitive = cfg.enable_loadaware and (
        usage_prod is not None
        or bool(dict(cfg.loadaware.prod_usage_thresholds))
    )
    is_prod = pods.priority_class == int(PriorityClass.PROD)
    pprod = jnp.pad(is_prod[order].astype(jnp.int32), (0, P_pad - P))
    if prod_sensitive:
        uprod = _rows(usage_prod if usage_prod is not None else usage_np, N_pad)
    else:
        uprod = None

    Q = max(quotas.runtime.shape[0], 1)
    Qp = -(-Q // LANES) * LANES
    qrt = _rows(quotas.runtime, Qp)
    qlim = _rows(quotas.limited.astype(jnp.int32), Qp)
    quse0 = _rows(quotas.used, Qp)

    weights = jnp.zeros((RP, LANES), jnp.int32)
    weights = weights.at[: res.NUM_RESOURCES, 0].set(
        jnp.asarray(res.weights_vector(dict(cfg.fit_resource_weights)), jnp.int32)
    )
    weights = weights.at[: res.NUM_RESOURCES, 1].set(
        jnp.asarray(
            res.weights_vector(dict(cfg.loadaware.resource_weights)), jnp.int32
        )
    )

    if extra_mask is not None or extra_scores is not None:
        if extra_mask is None:
            extra_mask = jnp.ones((P, N), bool)
        if extra_scores is None:
            extra_scores = jnp.zeros((P, N), jnp.int64)
        comb = jnp.where(
            extra_mask,
            extra_scores.astype(jnp.int32),
            jnp.int32(XCOMB_INFEASIBLE),
        )
        # sorted pod order on SUBLANES, nodes on lanes: [P_pad, N_pad]
        xcomb = jnp.pad(
            comb[order],
            ((0, P_pad - P), (0, N_pad - N)),
            constant_values=np.int32(XCOMB_INFEASIBLE),
        )
    else:
        xcomb = None

    usage_rows = _rows(usage_np, N_pad)
    n_gap = N_pad - mask_default.shape[0]
    for flag_row, vec in (
        (FLAG_ROW_OK, nodes.valid & mask_default),
        (FLAG_ROW_FRESH, nodes.metric_fresh),
        (FLAG_ROW_PROD_OK, nodes.valid & mask_prod),
    ):
        usage_rows = usage_rows.at[flag_row, :].set(
            jnp.pad(vec.astype(jnp.int32), (0, n_gap))
        )
    alloc_rows = _rows(nodes.allocatable, N_pad)
    req0_rows = _rows(nodes.requested, N_pad)

    chosen, nreq, nest, quse = _run_cycle_dense(
        preq,
        psreq,
        pest,
        qid,
        pprod,
        n_valid[None],
        alloc_rows,
        req0_rows,
        usage_rows,
        qrt,
        qlim,
        quse0,
        weights,
        uprod,
        xcomb,
        pgang,
        cfg=cfg,
        block=block,
        interpret=interpret,
    )

    assignment = jnp.full((P,), -1, jnp.int32).at[order].set(chosen[:P, 0])
    if has_gangs:
        assignment, status = strict_post_pass(assignment, gang, need, G)
    else:
        status = jnp.where(
            assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE
        )

    R = res.NUM_RESOURCES
    nq = quotas.used.shape[0]
    return CycleResult(
        assignment=assignment,
        status=status.astype(jnp.int32),
        node_requested=nreq[:R, :N].T.astype(jnp.int64),
        node_estimated=nest[:R, :N].T.astype(jnp.int64),
        quota_used=quse[:R, :nq].T.astype(jnp.int64),
        rounds=n_valid,
        path="pallas",
    )
