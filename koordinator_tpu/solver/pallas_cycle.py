"""The whole scheduling cycle as ONE Pallas TPU kernel.

``greedy_assign`` (solver/greedy.py) is semantically a 10k-step ``lax.scan``
whose per-step tensors are tiny ([nodes, resources]); on TPU its cost is
pure sequential dispatch latency (~55us/step), not FLOPs or bandwidth.  The
fix is TPU-native: the full cycle state — node requested/estimated tensors,
quota usage — is ~100 KB at 2k nodes, so it lives in VMEM for the whole
cycle and the per-pod loop runs *inside* a single kernel, eliminating the
inter-step overhead entirely (~10x on the 10k x 2k benchmark).

Layout: resources ride the 128-lane axis (R=13 used), nodes ride sublanes
([N, 128] i32 blocks); per-pod vectors stream in as (B, 128) blocks with a
grid over pod batches, and per-pod scalars (quota id, validity) arrive via
scalar prefetch in SMEM.  All score math is the same exact integer
arithmetic as ops/scoring.py — MiB resource units (model/resources.py)
guarantee every intermediate, including ``free * MaxNodeScore``, fits i32,
so no i64 emulation on the VPU.

Reference semantics mirrored (all paths under /root/reference): the per-pod
Filter/Score/Reserve cycle of ``pkg/scheduler/frameworkext`` with
NodeResourcesFit + LoadAware scoring and ElasticQuota admission; see
solver/greedy.py for the per-line citations — this kernel is bit-identical
with that scan (tests/test_pallas_cycle.py asserts it).
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
from koordinator_tpu.constraints.gang import gang_satisfaction
from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import MAX_NODE_SCORE, ClusterSnapshot
from koordinator_tpu.obs import devprof
from koordinator_tpu.ops.fit import nonzero_requests
from koordinator_tpu.ops.loadaware import (
    loadaware_node_masks,
    select_score_usage,
)
from koordinator_tpu.model.snapshot import PriorityClass
from koordinator_tpu.solver.greedy import (
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    STATUS_WAIT_GANG,
    CycleResult,
    queue_order,
)

LANES = 128
I32_MIN = np.int32(np.iinfo(np.int32).min)

# node flags ride the usage buffer's spare lanes (resources occupy only
# the first NUM_RESOURCES of 128; the LoadAware weight rows are zero
# beyond that, so flag lanes never contribute to any score)
FLAG_LANE_OK = 120  # valid & loadaware default mask
FLAG_LANE_FRESH = 121  # metric_fresh
FLAG_LANE_PROD_OK = 122  # valid & prod-threshold mask
# the initial node-requested vector rides alloc's spare lanes (one roll
# at init recovers it) — a dedicated req0 buffer cost 1MB of scoped VMEM
REQ0_LANE_OFFSET = 32
# the packing scheme silently corrupts real lanes if the resource axis
# ever grows into the borrowed regions — fail loudly instead
assert res.NUM_RESOURCES <= REQ0_LANE_OFFSET
assert REQ0_LANE_OFFSET + res.NUM_RESOURCES <= FLAG_LANE_OK
# combined extended-plugin tensor: score where feasible, sentinel where
# masked out (scores are magnitude-guarded < 2^29, far from the sentinel)
XCOMB_INFEASIBLE = I32_MIN


def _pad_rows(a: jnp.ndarray, rows: int) -> jnp.ndarray:
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _lanes(a: jnp.ndarray) -> jnp.ndarray:
    """[M, R] -> [M, 128] i32, resources on the lane axis."""
    return jnp.pad(a.astype(jnp.int32), ((0, 0), (0, LANES - a.shape[1])))


# In-kernel i32 scalar constants.  With x64 enabled, a bare Python scalar
# in jnp.where/floor_divide/etc. enters the traced sub-jaxpr as a weak
# i64[] argument whose i64->i32 convert has NO Mosaic lowering (it
# recurses forever in _convert_helper).  Every scalar that reaches kernel
# math must therefore be a strong i32.
def _i32(v) -> jnp.ndarray:
    return jnp.int32(v)


def _exact_div(v, safe, recip):
    """Exact nonnegative i32 floor division via f32 reciprocal.

    The VPU has no integer divide — Mosaic emulates ``lax.div`` in
    software, and ablation on v5e measured it at HALF the whole kernel's
    runtime (a [N,1] column div costs the same per-vreg as a full
    [N,128] one).  Every quotient in the score math is bounded by
    MAX_NODE_SCORE (=100): free/clamped <= cap and weighted sums divide
    by their weight total, so ``v/safe <= 100`` and the f32 rounding
    error (rel ~2^-22) is far below the +-1 a single correction step
    absorbs.  Exactness at floor boundaries is restored by the two
    corrections; parity tests assert bit-identity with ``//``.
    """
    q = (v.astype(jnp.float32) * recip).astype(jnp.int32)
    r = v - q * safe
    q = q + jnp.where(r >= safe, _i32(1), _i32(0))
    q = q - jnp.where(v - q * safe < _i32(0), _i32(1), _i32(0))
    return q


def _least_requested(t, cap, recip):
    """Exact ops/scoring.py least_requested_score in i32 (free pre-clamped
    so free * MAX_NODE_SCORE never overflows)."""
    safe = jnp.maximum(cap, _i32(1))
    # jnp.maximum, not jnp.clip: clip's asarray(0) bound is a strong i64
    # under x64 and i64 does not lower on Mosaic
    free = jnp.maximum(cap - t, _i32(0))
    score = _exact_div(free * _i32(MAX_NODE_SCORE), safe, recip)
    return jnp.where((cap == _i32(0)) | (t > cap), _i32(0), score)


def _most_requested(t, cap, recip):
    safe = jnp.maximum(cap, _i32(1))
    clamped = jnp.minimum(t, cap)
    score = _exact_div(clamped * _i32(MAX_NODE_SCORE), safe, recip)
    return jnp.where(cap == _i32(0), _i32(0), score)


def _weighted(per_res, w_row, w_sum: int):
    if w_sum == 0:
        return jnp.zeros(per_res.shape[:-1] + (1,), jnp.int32)
    # dtype=i32: under x64 jnp.sum accumulates i32 into i64 (numpy
    # semantics) and i64 does not lower on Mosaic
    s = jnp.sum(per_res * w_row, axis=-1, keepdims=True, dtype=jnp.int32)
    return _exact_div(s, _i32(w_sum), np.float32(1.0 / w_sum))


def _kernel_filter_fit(nreq, req, alloc):
    """NodeResourcesFit over a [rows, 128] state block: only requested
    resources constrain.  i32 violation count, not jnp.all: a bool lane
    reduction lowers to an i1 reduce_min Mosaic rejects ("Unsupported
    element type for the selected reduction")."""
    need = req > _i32(0)
    fviol = jnp.where(need & (nreq + req > alloc), _i32(1), _i32(0))
    return jnp.max(fviol, axis=-1, keepdims=True) == _i32(0)


def _kernel_scores(
    nreq, nest, alloc, usage, fresh, sreq, est, recip,
    fit_w_row, la_w_row, fit_w_sum, la_w_sum, cfg: CycleConfig,
):
    """The plugin Score sum in exact i32 over a [rows, 128] state block
    — the ONE in-kernel mirror of solver.greedy.step_feasible_scores,
    shared by the per-pod and wave kernels (flag lanes in ``usage``
    never contribute: the weight rows are zero beyond the resources)."""
    total = jnp.zeros((alloc.shape[0], 1), jnp.int32)
    if cfg.enable_fit_score:
        t = nreq + sreq
        if cfg.fit_scoring_strategy == MOST_ALLOCATED:
            per_res = _most_requested(t, alloc, recip)
        else:
            per_res = _least_requested(t, alloc, recip)
        total = total + _i32(cfg.fit_plugin_weight) * _weighted(
            per_res, fit_w_row, fit_w_sum
        )
    if cfg.enable_loadaware:
        est_used = usage + nest + est
        per_res = _least_requested(est_used, alloc, recip)
        la = _weighted(per_res, la_w_row, la_w_sum)
        total = total + _i32(cfg.loadaware_plugin_weight) * jnp.where(
            fresh, la, _i32(0)
        )
    return total


def _cycle_kernel(
    # scalar prefetch (SMEM)
    qid_ref,  # i32[P] quota id per sorted pod (-1 = none)
    pvalid_ref,  # i32[P]
    pprod_ref,  # i32[P] 1 = PriorityProd pod (prod filter/score selection)
    # inputs (VMEM)
    preq_ref,  # i32[B, 128] pod requests (sorted)
    psreq_ref,  # i32[B, 128] nonzero-default score requests
    pest_ref,  # i32[B, 128] estimator output
    alloc_ref,  # i32[N, 128]
    usage_ref,  # i32[N, 128] score usage (aggregated pre-selected on host);
    # spare lanes carry the node flags (FLAG_LANE_OK/FRESH/PROD_OK) — a
    # dedicated flags buffer cost 1MB of the 16MB scoped-VMEM budget for
    # three booleans per node
    qrt_ref,  # i32[Q, 128] quota runtime
    qlim_ref,  # i32[Q, 128] quota limited mask
    quse0_ref,  # i32[Q, 128] initial quota used
    w_ref,  # i32[8, 128] row0 = fit weights, row1 = loadaware weights
    *rest,  # optional: uprod_ref i32[N, 128] (prod-pods usage, has_prod);
    # optional: xcomb_ref i32[N, B] — the combined extended-plugin tensor
    # (NUMA/reservation/deviceshare): score where feasible,
    # XCOMB_INFEASIBLE where masked, pods on the lane axis so each step
    # extracts a [N, 1] column — then outputs
    block: int,
    cfg: CycleConfig,
    has_extras: bool,
    has_prod: bool,
):
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
    # the node/quota state carries IN the output refs (constant index
    # maps persist across grid steps): no separate scratch copies — at
    # benchmark scale the duplicated state alone overflowed the 16MB
    # scoped-VMEM limit once the extended-plugin tiles joined
    (chosen_ref, nreq_ref, nest_ref, quse_ref) = rest

    i = pl.program_id(0)

    @pl.when(i == _i32(0))
    def _init():
        # output buffers are NOT initialized on hardware (the standard
        # revisited-block contract: only what the kernel wrote persists),
        # so EVERY carried state needs an explicit i==0 init.  The initial
        # requested state rides alloc's spare lanes: one roll brings lanes
        # [REQ0_LANE_OFFSET, +R) down to [0, R), the rest zeroes.
        lane = lax.broadcasted_iota(jnp.int32, alloc_ref.shape, 1)
        rolled = pltpu.roll(alloc_ref[:], _i32(LANES - REQ0_LANE_OFFSET), 1)
        nreq_ref[:] = jnp.where(
            lane < _i32(res.NUM_RESOURCES), rolled, _i32(0)
        )
        nest_ref[:] = jnp.zeros_like(nest_ref)
        quse_ref[:] = quse0_ref[:]

    alloc = alloc_ref[:]
    n_rows = alloc.shape[0]
    node_ok = usage_ref[:, FLAG_LANE_OK : FLAG_LANE_OK + 1] != _i32(0)
    fresh = usage_ref[:, FLAG_LANE_FRESH : FLAG_LANE_FRESH + 1] != _i32(0)
    row_iota = lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)

    fit_w_row = w_ref[0:1, :]
    la_w_row = w_ref[1:2, :]
    # weight sums over the AXIS-MAPPED weights (names not on RESOURCE_AXIS
    # are dropped by weights_vector; the divisor must match the scan path)
    fit_w_sum = sum(res.weights_vector(dict(cfg.fit_resource_weights)))
    la_w_sum = sum(res.weights_vector(dict(cfg.loadaware.resource_weights)))
    # loop-invariant f32 reciprocal of node capacity for _exact_div
    recip = 1.0 / jnp.maximum(alloc, _i32(1)).astype(jnp.float32)

    def step(j, _):
        # j MUST stay i32: Mosaic has no i64 lowering, and with x64
        # enabled an int-typed fori_loop counter arrives as i64 — any
        # promotion it causes (p, pl.ds indices, lane compares) recurses
        # forever in _convert_helper at kernel-lowering time.
        p = i * block + j
        req = preq_ref[pl.ds(j, 1), :]  # [1, 128]
        sreq = psreq_ref[pl.ds(j, 1), :]
        est = pest_ref[pl.ds(j, 1), :]
        qid = qid_ref[p]
        is_valid = pvalid_ref[p] != _i32(0)
        qidx = jnp.maximum(qid, _i32(0))
        if has_prod:
            is_prod = pprod_ref[p] != _i32(0)
            # select the i32 flag lanes, compare after: a select over i1
            # vectors has no Mosaic legalization ('arith.select')
            node_ok_p = (
                jnp.where(
                    is_prod,
                    usage_ref[:, FLAG_LANE_PROD_OK : FLAG_LANE_PROD_OK + 1],
                    usage_ref[:, FLAG_LANE_OK : FLAG_LANE_OK + 1],
                )
                != _i32(0)
            )
            usage_p = jnp.where(is_prod, uprod_ref[:], usage_ref[:])
        else:
            node_ok_p = node_ok
            usage_p = usage_ref[:]

        nreq = nreq_ref[:]
        # Filter: Fit (only requested resources constrain) + node flags
        fits = _kernel_filter_fit(nreq, req, alloc)
        # ElasticQuota admission on limited dimensions
        quse_row = quse_ref[pl.ds(qidx, 1), :]
        # scalar reduce in i32 (a scalar bool `jnp.all` does not lower on
        # Mosaic: only 32-bit element types squeeze to scalars)
        qviol = jnp.where(
            (qlim_ref[pl.ds(qidx, 1), :] != _i32(0))
            & (quse_row + req > qrt_ref[pl.ds(qidx, 1), :]),
            jnp.int32(1),
            jnp.int32(0),
        )
        qok = jnp.max(qviol) == _i32(0)
        feasible = fits & node_ok_p & ((qid < _i32(0)) | qok) & is_valid
        if has_extras:
            # extract this pod's [N, 1] column by one-hot lane reduction
            # (dynamic lane slicing is costly on the VPU; a masked lane
            # sum is a single vector op); the sentinel encodes the mask
            lane = lax.broadcasted_iota(jnp.int32, (1, block), 1) == j
            xv = jnp.sum(
                jnp.where(lane, xcomb_ref[:], _i32(0)),
                axis=1,
                keepdims=True,
                dtype=jnp.int32,
            )
            feasible = feasible & (xv != _i32(XCOMB_INFEASIBLE))

        # Score: NodeResourcesFit + LoadAware, exact integer math
        total = _kernel_scores(
            nreq, nest_ref[:], alloc, usage_p, fresh, sreq, est, recip,
            fit_w_row, la_w_row, fit_w_sum, la_w_sum, cfg,
        )
        if has_extras:
            total = total + jnp.where(
                xv == _i32(XCOMB_INFEASIBLE), _i32(0), xv
            )

        masked = jnp.where(feasible, total, I32_MIN)
        best = jnp.max(masked)
        any_feasible = best > I32_MIN
        # first index achieving the max == jnp.argmax tie-break
        chosen = jnp.min(jnp.where(masked == best, row_iota, _i32(n_rows)))
        chosen = jnp.where(any_feasible, chosen, _i32(-1))

        # Reserve: commit the pod's resources to the chosen node / quota
        cidx = jnp.maximum(chosen, _i32(0))
        take = jnp.where(any_feasible, req, _i32(0))
        nreq_ref[pl.ds(cidx, 1), :] = nreq_ref[pl.ds(cidx, 1), :] + take
        nest_ref[pl.ds(cidx, 1), :] = nest_ref[pl.ds(cidx, 1), :] + jnp.where(
            any_feasible, est, _i32(0)
        )
        quse_ref[pl.ds(qidx, 1), :] = quse_row + jnp.where(
            any_feasible & (qid >= _i32(0)), req, _i32(0)
        )

        chosen_ref[pl.ds(j, 1), :] = jnp.full((1, LANES), chosen, jnp.int32)
        return jnp.int32(0)

    lax.fori_loop(jnp.int32(0), jnp.int32(block), step, jnp.int32(0))


def _wave_cycle_kernel(
    # scalar prefetch (SMEM)
    qid_ref,  # i32[P] quota id per sorted pod (-1 = none)
    pvalid_ref,  # i32[P]
    pprod_ref,  # i32[P]
    # inputs (VMEM) — same layout as _cycle_kernel
    preq_ref,
    psreq_ref,
    pest_ref,
    alloc_ref,
    usage_ref,
    qrt_ref,
    qlim_ref,
    quse0_ref,
    w_ref,
    *rest,
    block: int,
    cfg: CycleConfig,
    has_extras: bool,
    has_prod: bool,
    wave: int,
    top_m: int,
):
    """Wave-batched inner loop: the solver/wave.py rounds, in VMEM.

    Instead of one Filter/Score/argmax/Reserve dispatch per pod, each
    sequential round freezes the next ``wave`` pods' top-``top_m``
    candidate (score, node) pairs against round-start state, then
    resolves the wave in queue order with the SAME certification the
    jnp paths use — re-keyed candidates vs the frozen M-th key, queue
    prefix commits, node-invariant quota recheck.  Differences from the
    i64 resolver, both exactness-preserving:

    * keys stay UNPACKED (score, index) with a lexicographic compare —
      the packed ``score * N + idx`` key would overflow i32;
    * Reserve lands on the state refs LIVE during resolution, so a
      later pod's re-key reads frozen rows + earlier in-wave deltas
      directly (the frozen candidate keys were captured before any
      commit of the round);
    * the MostAllocated closed universe is refined to {own top-M} ∪
      {nodes committed-to earlier in the round}: commits land only on
      wave candidates, every other node's key-for-this-pod is frozen
      below its k_M, so re-keying that union bounds the true best
      exactly (docs/KERNEL.md "Wave batching").

    Waves never cross the 128-pod grid blocks the existing streaming
    provides (``wvalid`` masks the tail); wave segmentation does not
    affect placements, only round counts.  The round total accumulates
    in the stats output so callers can surface the sequential-round win.
    """
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
    (chosen_ref, nreq_ref, nest_ref, quse_ref, rounds_ref,
     cand_s_ref, cand_i_ref) = rest

    i = pl.program_id(0)
    W = wave
    n_rows = alloc_ref.shape[0]
    # the frozen candidate (score, index) slots live one-per-lane in the
    # 128-lane scratch rows, so M is capped at LANES as well as the node
    # count — a shallower M changes round counts, never placements (any
    # M >= 1 certifies exactly)
    M = max(1, min(top_m, n_rows, LANES))
    most_alloc = cfg.enable_fit_score and (
        cfg.fit_scoring_strategy == MOST_ALLOCATED
    )

    @pl.when(i == _i32(0))
    def _init():
        lane = lax.broadcasted_iota(jnp.int32, alloc_ref.shape, 1)
        rolled = pltpu.roll(alloc_ref[:], _i32(LANES - REQ0_LANE_OFFSET), 1)
        nreq_ref[:] = jnp.where(
            lane < _i32(res.NUM_RESOURCES), rolled, _i32(0)
        )
        nest_ref[:] = jnp.zeros_like(nest_ref)
        quse_ref[:] = quse0_ref[:]
        rounds_ref[:] = jnp.zeros_like(rounds_ref)

    alloc = alloc_ref[:]
    row_iota = lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
    lane_iota = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub_iota_w = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    fit_w_row = w_ref[0:1, :]
    la_w_row = w_ref[1:2, :]
    fit_w_sum = sum(res.weights_vector(dict(cfg.fit_resource_weights)))
    la_w_sum = sum(res.weights_vector(dict(cfg.loadaware.resource_weights)))
    recip = 1.0 / jnp.maximum(alloc, _i32(1)).astype(jnp.float32)

    def _lane(row, m):
        """Lane m (traced) of a [1, 128] row -> i32 scalar (dynamic lane
        slicing is costly on the VPU; a masked lane max is one vector op).
        Scalar reductions here are max, never sum: Mosaic lowers a
        scalar i32 sum through an i64 accumulator under x64, which v5e
        refuses ("64-bit types are not supported")."""
        return jnp.max(jnp.where(lane_iota == m, row, I32_MIN))

    def frozen_masked(j):
        """Frozen masked scores [n_rows, 1] for block-row j.  Quota is
        handled at resolution (node-invariant), matching one_pod_keys in
        the jnp wave paths."""
        p = i * block + j
        req = preq_ref[pl.ds(j, 1), :]
        sreq = psreq_ref[pl.ds(j, 1), :]
        est = pest_ref[pl.ds(j, 1), :]
        is_valid = pvalid_ref[p] != _i32(0)
        if has_prod:
            is_prod = pprod_ref[p] != _i32(0)
            node_ok_p = (
                jnp.where(
                    is_prod,
                    usage_ref[:, FLAG_LANE_PROD_OK : FLAG_LANE_PROD_OK + 1],
                    usage_ref[:, FLAG_LANE_OK : FLAG_LANE_OK + 1],
                )
                != _i32(0)
            )
            usage_p = jnp.where(is_prod, uprod_ref[:], usage_ref[:])
        else:
            node_ok_p = (
                usage_ref[:, FLAG_LANE_OK : FLAG_LANE_OK + 1] != _i32(0)
            )
            usage_p = usage_ref[:]
        fresh = usage_ref[:, FLAG_LANE_FRESH : FLAG_LANE_FRESH + 1] != _i32(0)
        feasible = (
            _kernel_filter_fit(nreq_ref[:], req, alloc)
            & node_ok_p
            & is_valid
        )
        total = _kernel_scores(
            nreq_ref[:], nest_ref[:], alloc, usage_p, fresh, sreq, est,
            recip, fit_w_row, la_w_row, fit_w_sum, la_w_sum, cfg,
        )
        if has_extras:
            xv = jnp.sum(
                jnp.where(lane_iota == j, xcomb_ref[:], _i32(0)),
                axis=1,
                keepdims=True,
                dtype=jnp.int32,
            )
            feasible = feasible & (xv != _i32(XCOMB_INFEASIBLE))
            total = total + jnp.where(
                xv == _i32(XCOMB_INFEASIBLE), _i32(0), xv
            )
        return jnp.where(feasible, total, I32_MIN)

    def rekey(c, j, req, sreq, est, is_prod):
        """Current score of node c for the pod at block-row j, or
        I32_MIN when infeasible.  The state refs already carry this
        round's earlier commits (live Reserve), so the read IS frozen
        rows + in-wave deltas — the same quantity the i64 resolver
        reconstructs from gathered rows."""
        a = alloc_ref[pl.ds(c, 1), :]
        nr = nreq_ref[pl.ds(c, 1), :]
        ne = nest_ref[pl.ds(c, 1), :]
        u_row = usage_ref[pl.ds(c, 1), :]
        fresh = u_row[:, FLAG_LANE_FRESH : FLAG_LANE_FRESH + 1] != _i32(0)
        if has_prod:
            ok_col = (
                jnp.where(
                    is_prod,
                    u_row[:, FLAG_LANE_PROD_OK : FLAG_LANE_PROD_OK + 1],
                    u_row[:, FLAG_LANE_OK : FLAG_LANE_OK + 1],
                )
                != _i32(0)
            )
            usage_row = jnp.where(is_prod, uprod_ref[pl.ds(c, 1), :], u_row)
        else:
            ok_col = u_row[:, FLAG_LANE_OK : FLAG_LANE_OK + 1] != _i32(0)
            usage_row = u_row
        recip_c = 1.0 / jnp.maximum(a, _i32(1)).astype(jnp.float32)
        feas = _kernel_filter_fit(nr, req, a) & ok_col  # [1, 1]
        total = _kernel_scores(
            nr, ne, a, usage_row, fresh, sreq, est, recip_c,
            fit_w_row, la_w_row, fit_w_sum, la_w_sum, cfg,
        )
        feas_s = jnp.max(jnp.where(feas, _i32(1), _i32(0))) != _i32(0)
        score = jnp.max(total)  # [1, 1]: the one element
        if has_extras:
            xv = _lane(xcomb_ref[pl.ds(c, 1), :], j)
            feas_s = feas_s & (xv != _i32(XCOMB_INFEASIBLE))
            score = score + jnp.where(
                xv == _i32(XCOMB_INFEASIBLE), _i32(0), xv
            )
        return jnp.where(feas_s, score, I32_MIN)

    def wave_round(carry):
        ptr, rounds = carry

        # Phase A: freeze the wave's top-M (score, node) pairs against
        # round-start state (no ref is written until resolution below)
        def score_one(w, _):
            j = ptr + w
            in_block = j < _i32(block)
            j_eff = jnp.minimum(j, _i32(block - 1))
            masked = jnp.where(in_block, frozen_masked(j_eff), I32_MIN)
            srow = jnp.full((1, LANES), I32_MIN, jnp.int32)
            irow = jnp.zeros((1, LANES), jnp.int32)

            def pick(m, st):
                rem, srow, irow = st
                best = jnp.max(rem)
                # first index achieving the max == jnp.argmax tie-break
                bidx = jnp.min(
                    jnp.where(rem == best, row_iota, _i32(n_rows))
                )
                srow = jnp.where(lane_iota == m, best, srow)
                irow = jnp.where(lane_iota == m, bidx, irow)
                rem = jnp.where(row_iota == bidx, I32_MIN, rem)
                return (rem, srow, irow)

            _, srow, irow = lax.fori_loop(
                jnp.int32(0), jnp.int32(M), pick, (masked, srow, irow)
            )
            cand_s_ref[pl.ds(w, 1), :] = srow
            cand_i_ref[pl.ds(w, 1), :] = irow
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(W), score_one, jnp.int32(0))

        # Phase B: resolve the wave in queue order (solver/wave.py
        # resolve_wave semantics, i32)
        def resolve(i_w, st):
            choices_col, committed_col, active, ncommit = st
            j = ptr + i_w
            in_block = j < _i32(block)
            j_eff = jnp.minimum(j, _i32(block - 1))
            p = i * block + j_eff
            req = preq_ref[pl.ds(j_eff, 1), :]
            sreq = psreq_ref[pl.ds(j_eff, 1), :]
            est = pest_ref[pl.ds(j_eff, 1), :]
            qid = qid_ref[p]
            qidx = jnp.maximum(qid, _i32(0))
            is_valid = pvalid_ref[p] != _i32(0)
            is_prod = (pprod_ref[p] != _i32(0)) if has_prod else None
            srow = cand_s_ref[pl.ds(i_w, 1), :]
            irow = cand_i_ref[pl.ds(i_w, 1), :]
            k_s = _lane(srow, _i32(M - 1))
            k_i = _lane(irow, _i32(M - 1))
            # k_M at sentinel: every frozen-feasible node is already a
            # candidate, and committed load never turns an infeasible
            # node feasible
            sentinel_m = k_s == I32_MIN

            # current best over the pod's own candidates — unpacked
            # (score, lowest-index) lexicographic max
            bs = I32_MIN
            bi = _i32(0)
            for m in range(M):  # static unroll, M is tiny
                c = _lane(irow, _i32(m))
                fs = _lane(srow, _i32(m))
                cs = rekey(c, j_eff, req, sreq, est, is_prod)
                # a sentinel slot (fewer than m+1 frozen-feasible nodes)
                # stays sentinel: its index is not a real candidate
                cs = jnp.where(fs == I32_MIN, I32_MIN, cs)
                better = (cs > bs) | ((cs == bs) & (c < bi))
                bs = jnp.where(better, cs, bs)
                bi = jnp.where(better, c, bi)

            if most_alloc:
                # refined closed universe (kernel docstring): nodes
                # committed-to earlier this round are the only
                # non-candidates whose keys moved
                def consider(w, st2):
                    bs2, bi2 = st2
                    cw = jnp.max(
                        jnp.where(sub_iota_w == w, choices_col, I32_MIN)
                    )
                    comm = jnp.max(
                        jnp.where(sub_iota_w == w, committed_col, _i32(0))
                    ) != _i32(0)
                    live = comm & (w < i_w)
                    cw_eff = jnp.maximum(cw, _i32(0))
                    cs2 = jnp.where(
                        live,
                        rekey(cw_eff, j_eff, req, sreq, est, is_prod),
                        I32_MIN,
                    )
                    better2 = (cs2 > bs2) | ((cs2 == bs2) & (cw_eff < bi2))
                    return (
                        jnp.where(better2, cs2, bs2),
                        jnp.where(better2, cw_eff, bi2),
                    )

                bs, bi = lax.fori_loop(
                    jnp.int32(0), jnp.int32(W), consider, (bs, bi)
                )
                lex_ge = (bs > k_s) | ((bs == k_s) & (bi <= k_i))
                # pod 0 has no earlier in-wave commits: frozen keys ARE
                # current (liveness)
                certified = lex_ge | sentinel_m | (i_w == _i32(0))
            else:
                lex_ge = (bs > k_s) | ((bs == k_s) & (bi <= k_i))
                certified = lex_ge | sentinel_m
            feas = bs > I32_MIN

            # ElasticQuota admission against the LIVE in-wave quota state
            quse_row = quse_ref[pl.ds(qidx, 1), :]
            qviol = jnp.where(
                (qlim_ref[pl.ds(qidx, 1), :] != _i32(0))
                & (quse_row + req > qrt_ref[pl.ds(qidx, 1), :]),
                jnp.int32(1),
                jnp.int32(0),
            )
            qblocked = (qid >= _i32(0)) & (jnp.max(qviol) != _i32(0))
            usable = is_valid & ~qblocked & in_block
            choice = jnp.where(feas & usable, bi, _i32(-1))
            # a -1 outcome certifies only when node-independent or at
            # the sentinel (see solver/wave.py) — otherwise the pod ends
            # the commit prefix and reruns next round
            certified = certified | ~usable
            active_b = active != _i32(0)
            commit = active_b & certified
            take_node = commit & (choice >= _i32(0))

            # live Reserve: later pods re-key against these rows
            cidx = jnp.maximum(choice, _i32(0))
            take = jnp.where(take_node, req, _i32(0))
            nreq_ref[pl.ds(cidx, 1), :] = nreq_ref[pl.ds(cidx, 1), :] + take
            nest_ref[pl.ds(cidx, 1), :] = nest_ref[
                pl.ds(cidx, 1), :
            ] + jnp.where(take_node, est, _i32(0))
            quse_ref[pl.ds(qidx, 1), :] = quse_row + jnp.where(
                take_node & (qid >= _i32(0)), req, _i32(0)
            )

            # uncommitted rows keep their value: they rerun in a later
            # round (the committed set is always a queue prefix)
            prev = chosen_ref[pl.ds(j_eff, 1), :]
            chosen_ref[pl.ds(j_eff, 1), :] = jnp.where(
                commit & in_block, choice, prev
            )

            choices_col = jnp.where(
                sub_iota_w == i_w,
                jnp.where(take_node, choice, _i32(-1)),
                choices_col,
            )
            committed_col = jnp.where(
                sub_iota_w == i_w,
                jnp.where(take_node, _i32(1), _i32(0)),
                committed_col,
            )
            ncommit = ncommit + jnp.where(commit, _i32(1), _i32(0))
            active = jnp.where(commit, active, _i32(0))
            return (choices_col, committed_col, active, ncommit)

        st0 = (
            jnp.full((W, 1), -1, jnp.int32),
            jnp.zeros((W, 1), jnp.int32),
            jnp.int32(1),
            jnp.int32(0),
        )
        _, _, _, ncommit = lax.fori_loop(
            jnp.int32(0), jnp.int32(W), resolve, st0
        )
        return (ptr + ncommit, rounds + _i32(1))

    _, rounds = lax.while_loop(
        lambda c: c[0] < _i32(block),
        wave_round,
        (jnp.int32(0), jnp.int32(0)),
    )
    rounds_ref[:] = rounds_ref[:] + rounds


@devprof.boundary("solver.pallas_cycle._run_cycle")
@partial(jax.jit, static_argnames=("cfg", "block", "interpret", "wave", "top_m"))
def _run_cycle(
    preq, psreq, pest, qid, pvalid, pprod, alloc, usage, qrt,
    qlim, quse0, weights, uprod=None, xcomb=None, *,
    cfg: CycleConfig, block: int, interpret: bool,
    wave: int = 0, top_m: int = 0
):
    P = preq.shape[0]
    N = alloc.shape[0]
    Q = qrt.shape[0]
    has_extras = xcomb is not None
    has_prod = uprod is not None
    grid = (P // block,)
    # index maps return strong-i32 zeros: with x64 on, a literal 0 becomes
    # an i64 constant in the lowered index-map func, which Mosaic rejects
    # ("failed to legalize operation 'func.func'")
    _z = np.int32(0)
    node_spec = pl.BlockSpec((N, LANES), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM)
    quota_spec = pl.BlockSpec((Q, LANES), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM)
    pod_spec = pl.BlockSpec((block, LANES), lambda i, *_: (i, _z), memory_space=pltpu.VMEM)
    in_specs = (
        [pod_spec, pod_spec, pod_spec]
        + [node_spec] * 2
        + [quota_spec] * 3
        + [pl.BlockSpec((8, LANES), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM)]
    )
    operands = [preq, psreq, pest, alloc, usage, qrt, qlim, quse0, weights]
    if has_prod:
        in_specs += [node_spec]
        operands += [uprod]
    if has_extras:
        # [N, P] with pods on lanes: each grid step streams a (N, block) tile
        xtra_spec = pl.BlockSpec(
            (N, block), lambda i, *_: (_z, i), memory_space=pltpu.VMEM
        )
        in_specs += [xtra_spec]
        operands += [xcomb]
    out_specs = [pod_spec, node_spec, node_spec, quota_spec]
    out_shape = [
        jax.ShapeDtypeStruct((P, LANES), jnp.int32),
        jax.ShapeDtypeStruct((N, LANES), jnp.int32),
        jax.ShapeDtypeStruct((N, LANES), jnp.int32),
        jax.ShapeDtypeStruct((Q, LANES), jnp.int32),
    ]
    if wave > 1:
        # wave-batched inner loop: the round-count stats row joins the
        # outputs and the frozen candidate tables ride scratch VMEM
        W_k = min(wave, block)  # waves never cross the 128-pod blocks
        kernel = partial(
            _wave_cycle_kernel,
            block=block,
            cfg=cfg,
            has_extras=has_extras,
            has_prod=has_prod,
            wave=W_k,
            top_m=top_m,
        )
        out_specs = out_specs + [
            pl.BlockSpec(
                (8, LANES), lambda i, *_: (_z, _z), memory_space=pltpu.VMEM
            )
        ]
        out_shape = out_shape + [jax.ShapeDtypeStruct((8, LANES), jnp.int32)]
        scratch_shapes = [
            pltpu.VMEM((W_k, LANES), jnp.int32),  # frozen cand scores
            pltpu.VMEM((W_k, LANES), jnp.int32),  # frozen cand indices
        ]
    else:
        kernel = partial(
            _cycle_kernel,
            block=block,
            cfg=cfg,
            has_extras=has_extras,
            has_prod=has_prod,
        )
        scratch_shapes = []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(qid, pvalid, pprod, *operands)


def greedy_assign_pallas(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    interpret: bool = False,
    extra_mask=None,  # bool[P, N] extended-plugin Filter tensor
    extra_scores=None,  # i64[P, N] extended-plugin Score tensor
) -> CycleResult:
    """Drop-in replacement for solver.greedy.greedy_assign on TPU.

    ``cfg.wave > 1`` swaps the per-pod inner loop for the wave-batched
    rounds (``_wave_cycle_kernel``, docs/KERNEL.md "Wave batching") —
    bit-identical placements, ``rounds`` set on the result.

    Raises ValueError when ``extra_scores`` exceed the i32 headroom the
    kernel's accumulation needs — direct callers must not get silent
    wraparound and divergent placements (the run_cycle dispatcher checks the
    same bound before routing here; this guards everyone else).
    """
    if extra_scores is not None:
        import numpy as _np

        peak = int(jnp.max(jnp.abs(extra_scores)))
        if peak >= 2**29:
            raise ValueError(
                f"extra_scores magnitude {peak} >= 2^29: out of the Pallas "
                "kernel's i32 headroom; use the lax.scan path (greedy_assign)"
            )
    return _greedy_assign_pallas(
        snapshot, cfg, interpret, extra_mask, extra_scores
    )


@devprof.boundary("solver.pallas_cycle._greedy_assign_pallas")
@partial(jax.jit, static_argnames=("cfg", "interpret"))
def _greedy_assign_pallas(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    interpret: bool = False,
    extra_mask=None,  # bool[P, N] extended-plugin Filter tensor
    extra_scores=None,  # i64[P, N] extended-plugin Score tensor
) -> CycleResult:
    """jit inner of greedy_assign_pallas (magnitude-checked wrapper above).

    Bit-identical placements (same queue order, same integer scores, same
    argmax tie-breaks); i32 internally — sound because MiB/milli units bound
    every intermediate (documented in model/resources.py).  The extended
    plugins' (NUMA/reservation/deviceshare — scheduler/plugins.py) stateless
    Filter/Score tensors ride the kernel as [N, P] tiles so the full plugin
    composition stays on the single-kernel path (reference analog: these
    plugins run inside the Score hot loop, ``nodenumaresource/scoring.go:55``).
    """
    pods, nodes, gangs, quotas = (
        snapshot.pods,
        snapshot.nodes,
        snapshot.gangs,
        snapshot.quotas,
    )
    P = pods.capacity
    N = nodes.allocatable.shape[0]

    order = queue_order(pods.priority, pods.valid)
    # pods always pad to 128-blocks: the extended-plugin tiles put pods on
    # the LANE axis ([N, block]), and a lane tile that is neither 128-wide
    # nor the full array does not lower on TPU
    P_pad = -(-P // 128) * 128
    block = 128
    N_pad = -(-N // 8) * 8

    def _pods(a):
        return _pad_rows(_lanes(a[order]), P_pad)

    preq = _pods(pods.requests)
    psreq = _pods(nonzero_requests(pods.requests))
    pest = _pods(pods.estimated)
    qid = jnp.pad(pods.quota_id[order].astype(jnp.int32), (0, P_pad - P))
    pvalid = jnp.pad(pods.valid[order].astype(jnp.int32), (0, P_pad - P))

    # LoadAware masks + score-usage selection (aggregated/prod profiles):
    # aggregated percentiles are selected host-side (static config), only
    # the prod-vs-default choice is per-pod and rides into the kernel
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
        uprod = _pad_rows(
            _lanes(usage_prod if usage_prod is not None else usage_np), N_pad
        )
    else:
        uprod = None

    Q = max(8, quotas.runtime.shape[0])
    Q = -(-Q // 8) * 8
    qrt = _pad_rows(_lanes(quotas.runtime), Q)
    qlim = _pad_rows(_lanes(quotas.limited.astype(jnp.int32)), Q)
    quse0 = _pad_rows(_lanes(quotas.used), Q)

    weights = jnp.zeros((8, LANES), jnp.int32)
    weights = weights.at[0, : res.NUM_RESOURCES].set(
        jnp.asarray(res.weights_vector(dict(cfg.fit_resource_weights)), jnp.int32)
    )
    weights = weights.at[1, : res.NUM_RESOURCES].set(
        jnp.asarray(
            res.weights_vector(dict(cfg.loadaware.resource_weights)), jnp.int32
        )
    )

    if extra_mask is not None or extra_scores is not None:
        # sorted pod order on the LANE axis, nodes on sublanes: [N_pad,
        # P_pad]; ONE combined tensor — score where feasible, sentinel
        # where masked (halves the streamed VMEM tiles)
        if extra_mask is None:
            extra_mask = jnp.ones((P, N), bool)
        if extra_scores is None:
            extra_scores = jnp.zeros((P, N), jnp.int64)
        comb = jnp.where(
            extra_mask,
            extra_scores.astype(jnp.int32),
            jnp.int32(XCOMB_INFEASIBLE),
        )
        xcomb = jnp.pad(
            comb[order].T,
            ((0, N_pad - N), (0, P_pad - P)),
            constant_values=np.int32(XCOMB_INFEASIBLE),
        )
    else:
        xcomb = None

    usage_with_flags = _pad_rows(_lanes(usage_np), N_pad)
    n_gap = N_pad - mask_default.shape[0]
    for flag_lane, vec in (
        (FLAG_LANE_OK, nodes.valid & mask_default),
        (FLAG_LANE_FRESH, nodes.metric_fresh),
        (FLAG_LANE_PROD_OK, nodes.valid & mask_prod),
    ):
        usage_with_flags = usage_with_flags.at[:, flag_lane].set(
            jnp.pad(vec.astype(jnp.int32), (0, n_gap))
        )
    # the initial requested vector rides alloc's spare lanes
    alloc_packed = _pad_rows(_lanes(nodes.allocatable), N_pad)
    req0 = _pad_rows(_lanes(nodes.requested), N_pad)
    alloc_packed = lax.dynamic_update_slice(
        alloc_packed,
        req0[:, : res.NUM_RESOURCES],
        (0, REQ0_LANE_OFFSET),
    )
    use_wave = cfg.wave > 1
    outs = _run_cycle(
        preq,
        psreq,
        pest,
        qid,
        pvalid,
        pprod,
        alloc_packed,
        usage_with_flags,
        qrt,
        qlim,
        quse0,
        weights,
        uprod,
        xcomb,
        cfg=cfg,
        block=block,
        interpret=interpret,
        wave=cfg.wave if use_wave else 0,
        top_m=cfg.top_m if use_wave else 0,
    )
    if use_wave:
        chosen, nreq, nest, quse, stats = outs
        rounds = stats[0, 0].astype(jnp.int64)
    else:
        chosen, nreq, nest, quse = outs
        rounds = None

    assignment = jnp.full((P,), -1, jnp.int32).at[order].set(chosen[:P, 0])
    # no Strict gang rollback in this engine (run_cycle and the bridge
    # send it no gang): a gang short of minMember only WAITs here
    status = jnp.where(assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE)
    assigned = (assignment >= 0) & pods.valid
    _, pod_gang_ok = gang_satisfaction(
        assignment, pods.valid, pods.gang_id, gangs.min_member
    )
    status = jnp.where(assigned & ~pod_gang_ok, STATUS_WAIT_GANG, status)

    R = res.NUM_RESOURCES
    nq = quotas.used.shape[0]
    return CycleResult(
        assignment=assignment,
        status=status.astype(jnp.int32),
        node_requested=nreq[:N, :R].astype(jnp.int64),
        node_estimated=nest[:N, :R].astype(jnp.int64),
        quota_used=quse[:nq, :R].astype(jnp.int64),
        rounds=rounds,
        path="pallas",
    )
