"""Wave/top-M certification: the shared math of the round-based cycle.

``greedy_assign`` replays the reference's one-pod-at-a-time cycle, so any
batched variant must prove each pod's choice equals what the sequential
scan would have picked.  Both round-based paths — the multi-chip
``parallel/shard_assign.py greedy_assign_waves`` and the single-chip
``wave_assign`` below — share that proof, so its primitives live here
exactly once:

* the packed (score, node) key: ``score * N + (N - 1 - node)``.  One
  integer max selects the highest score with the LOWEST node index — the
  same tie-break as ``jnp.argmax`` in the scan path — and keys are unique
  (the index term), which the certification argument leans on;
* the in-wave resolution ``resolve_wave``: every pod of a wave froze its
  global top-M candidate keys against round-start state; pods resolve in
  queue order, replaying earlier in-wave commits (node requested /
  estimated deltas, quota deltas) onto the candidates and certifying the
  winner against the frozen M-th key ``k_M``.  The first pod that cannot
  be certified ends the commit prefix — it and everything after rerun
  next round against fresh state.

Certification, in full (the part a maintainer can silently break; also
docs/KERNEL.md "Wave batching"):

* under LeastAllocated scoring keys are non-increasing as load commits,
  so any node outside a pod's frozen top-M stays strictly below the
  frozen ``k_M`` forever within the wave — re-keying the M candidates is
  enough, and the choice is EXACT whenever the best current candidate
  key is still >= ``k_M``;
* under MostAllocated keys INCREASE with committed load, which inverts
  that bound.  The symmetric certificate rides the CLOSED candidate
  universe: every in-wave commit lands on some wave pod's candidate, so
  the union of all wave pods' top-M rows is the only set of nodes whose
  keys can move within the round.  Each pod re-keys that whole universe
  exactly and certifies when the universe best >= its own frozen
  ``k_M``; packed-key uniqueness turns the boundary case into candidate
  membership.  Pod 0 of a round has no earlier in-wave commits, so it
  always commits — liveness holds for both strategies;
* quota admission is node-invariant, so it is rechecked exactly against
  the in-wave quota state: a blocked pod commits as unschedulable with
  no rescan.  A ``-1`` outcome certifies ONLY when it is
  node-independent or when ``k_M`` sits at the sentinel (fewer than M
  frozen-feasible nodes exist, and committed load never turns an
  infeasible node feasible under either strategy).

The Pallas kernel (solver/pallas_cycle.py) mirrors this resolution in
i32 with an unpacked (score, index) lexicographic compare — the packed
key would overflow i32 — and tests/test_parity_fuzz.py holds all three
implementations bit-identical to the scan.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from koordinator_tpu.config import (
    CycleConfig,
    DEFAULT_CYCLE_CONFIG,
    MOST_ALLOCATED,
)
from koordinator_tpu.constraints.gang import gang_satisfaction
from koordinator_tpu.model.snapshot import ClusterSnapshot, PriorityClass
from koordinator_tpu.obs import devprof
from koordinator_tpu.ops.fit import nonzero_requests
from koordinator_tpu.ops.loadaware import (
    loadaware_node_masks,
    select_score_usage,
)
from koordinator_tpu.solver.greedy import (
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    STATUS_WAIT_GANG,
    CycleResult,
    queue_order,
    step_feasible_scores,
)

# scores are bounded by plugin weights * MAX_NODE_SCORE (tiny); this
# sentinel for infeasible nodes leaves the packed key far from i64 limits
SENTINEL_SCORE = jnp.int64(-(2**40))


def is_most_allocated(cfg: CycleConfig) -> bool:
    """True when the fit strategy needs the closed-universe certificate
    (scores increase with committed load) instead of the k_M bound."""
    return bool(cfg.enable_fit_score) and (
        cfg.fit_scoring_strategy == MOST_ALLOCATED
    )


def sentinel_threshold(n_total: int):
    """Packed keys at or below this decode as infeasible."""
    return SENTINEL_SCORE * n_total // 2


def pack_keys(total, feasible, node_index, n_total: int):
    """(score, node) -> packed i64 key; infeasible slots take the
    sentinel score but KEEP their index term, so sentinel keys stay
    unique and order by node index like feasible ones."""
    idx_term = n_total - 1 - node_index
    return (
        jnp.where(feasible, total, SENTINEL_SCORE) * n_total + idx_term
    )


def decode_key(key, n_total: int):
    """Packed key -> (score, node i32).  Floor division decodes the
    negative sentinel range too."""
    score = key // n_total
    node = (n_total - 1 - (key - score * n_total)).astype(jnp.int32)
    return score, node


def score_feasible(score):
    """True when a DECODED score (not a packed key) is a real score
    rather than the infeasible sentinel."""
    return score > SENTINEL_SCORE // 2


def hit_sum(hit, rows):
    """``sum_w hit[w, k] * rows[w, r]`` for a bool ``hit`` [W, K] and
    i64 ``rows`` [W, R] -> [K, R], exact.  A masked sum, not an einsum:
    the TPU has no i64 dot and XLA's X64 rewrite implements none."""
    return jnp.sum(jnp.where(hit[:, :, None], rows[:, None, :], 0), axis=0)


def flatten_shards(a):
    """Align an ``all_gather``'d per-shard candidate payload by wave
    lane: ``[S, W, M, ...] -> [W, S*M, ...]``.  The flattened axis is
    the pool the cross-shard top-M merge selects from."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], -1) + a.shape[3:])


def merge_topm_keys(gathered_key, top_m: int):
    """Key-only cross-shard merge: the frozen global top-M packed keys
    per wave pod (ONE ``lax.top_k`` over the flattened ``[W, S*M]``
    pool).  The packed-key tie-break (highest score, lowest node index)
    rides the key encoding itself, so this merge orders identically to
    the scan path's ``pmax``/``argmax`` — the MostAllocated universe
    certificate needs only the resulting ``k_M`` bar."""
    cand_key, _ = lax.top_k(flatten_shards(gathered_key), top_m)
    return cand_key


def merge_topm(gathered: dict, top_m: int):
    """The full cross-shard top-M merge for the k_M (LeastAllocated)
    path: flatten every gathered row ``[S, W, M, ...] -> [W, S*M, ...]``,
    select the global top-M by packed ``key``, and gather each winner's
    state rows along.  Returns ``(cand_key i64[W, M], cand dict)`` in
    exactly the shape :func:`resolve_wave` consumes — the one merge
    collective's worth of data every shard reduces identically, keeping
    the round bit-identical to the single-chip oracle."""
    g = {k: flatten_shards(v) for k, v in gathered.items()}
    gkeys, gsel = lax.top_k(g["key"], top_m)

    def take(a):
        sel = gsel
        while sel.ndim < a.ndim:
            sel = sel[..., None]
        return jnp.take_along_axis(a, sel, axis=1)

    cand = {k: take(v) for k, v in g.items() if k != "key"}
    return gkeys, cand


def resolve_wave(
    cand_key,  # i64[W, M] frozen global top-M keys per wave pod
    *,
    cand: Optional[dict] = None,  # k_M path candidate rows (see below)
    universe: Optional[dict] = None,  # closed-universe rows (MostAllocated)
    preq_wave,  # i64[W, R] pod requests, wave order
    pest_wave,  # i64[W, R]
    psreq_wave,  # i64[W, R] nonzero-default score requests
    pqid_wave,  # i32[W]
    pvalid_wave,  # bool[W]
    pprod_wave,  # bool[W]
    wvalid,  # bool[W] lane addresses a real pod slot
    qrt,  # i64[Q, R] quota runtime
    qlim,  # bool[Q, R]
    quse,  # i64[Q, R] quota used at round start
    cfg: CycleConfig,
    n_total: int,
    prod_sensitive: bool,
):
    """Deterministic in-wave resolution + certification (module docstring).

    ``cand`` (LeastAllocated-style k_M path) carries per-pod candidate
    rows, each ``[W, M, ...]``: ``gid`` (i64 node ids), ``alloc``,
    ``nreq``, ``nest``, ``usage`` (prod-selected), ``ok``, ``fresh``,
    ``xval``, ``xfeas``.  ``universe`` (MostAllocated) carries the
    node-keyed closed candidate set, ``[U, ...]``: ``gid``, ``alloc``,
    ``nreq``, ``nest``, ``usage``, ``okd``, ``fresh``, plus per-pod
    ``xval``/``xfeas`` ``[W, U]`` and, when ``prod_sensitive``,
    ``uprod``/``okp``.  Duplicated nodes are harmless — identical rows
    produce identical keys.

    Returns ``(choices i64[W], committed bool[W], done bool[W],
    quota_used, ncommit i64)``; ``done`` marks the committed prefix
    (including -1 commits), ``committed`` the subset that took a node.
    """
    W, M = cand_key.shape
    N = n_total
    most_alloc = is_most_allocated(cfg)
    if most_alloc and universe is None:
        raise ValueError(
            "MostAllocated wave resolution needs the closed candidate "
            "universe (scores rise with committed load; the k_M bound "
            "alone is not exact)"
        )
    if not most_alloc and cand is None:
        raise ValueError("wave resolution needs the candidate rows")
    SENT_TH = sentinel_threshold(N)
    iota_w = jnp.arange(W)
    if most_alloc:
        u_gid = universe["gid"]

    def resolve(i, st):
        choices, committed, active, done, quse_w, ncommit = st
        req = preq_wave[i]
        est = pest_wave[i]
        sreq = psreq_wave[i]
        qid = pqid_wave[i]
        qi = jnp.maximum(qid, 0)
        earlier = committed & (iota_w < i)

        k_m = cand_key[i, M - 1]
        # k_M at sentinel: fewer than M nodes were feasible at frozen
        # state, so ALL feasible nodes are candidates — and committed
        # load never turns an infeasible node feasible under either
        # strategy
        sentinel_m = k_m <= SENT_TH

        if most_alloc:
            # universe certificate (module docstring): re-key the WHOLE
            # closed candidate universe exactly for this pod — frozen
            # rows + the in-wave commit deltas — then certify against
            # the frozen k_M
            hit_u = earlier[:, None] & (
                choices[:, None] == u_gid[None, :]
            )  # [W, U]
            dreq_u = hit_sum(hit_u, preq_wave)
            dest_u = hit_sum(hit_u, pest_wave)
            if prod_sensitive:
                usage_u = jnp.where(
                    pprod_wave[i], universe["uprod"], universe["usage"]
                )
                ok_u = jnp.where(
                    pprod_wave[i], universe["okp"], universe["okd"]
                )
            else:
                usage_u = universe["usage"]
                ok_u = universe["okd"]
            re_feas, re_total = step_feasible_scores(
                universe["nreq"] + dreq_u,
                universe["nest"] + dest_u,
                quse_w,
                universe["alloc"],
                usage_u,
                universe["fresh"],
                ok_u,
                req,
                sreq,
                est,
                jnp.int32(-1),
                jnp.bool_(True),
                qrt,
                qlim,
                cfg,
            )
            re_total = re_total + jnp.where(
                universe["xfeas"][i], universe["xval"][i], 0
            )
            re_feas = re_feas & universe["xfeas"][i]
            cur = pack_keys(re_total, re_feas, u_gid, N)  # [U]
            best_key = jnp.max(cur)
            best_node = u_gid[jnp.argmax(cur)]
            # pod 0 has no earlier in-wave commits: frozen keys are
            # current, its frozen top-1 is in the universe (liveness:
            # every round commits at least one pod)
            certified = (best_key >= k_m) | sentinel_m | (i == 0)
        else:
            # candidate current keys (recomputed when dirtied in-wave)
            c_nodes = cand["gid"][i]  # [M]
            hit = earlier[:, None] & (
                choices[:, None] == c_nodes[None, :]
            )  # [W, M]
            dreq = hit_sum(hit, preq_wave)
            dest = hit_sum(hit, pest_wave)
            dirty = jnp.any(hit, axis=0)  # [M]
            # re-key dirtied candidates with the SAME step semantics the
            # scan path and the frozen wave scoring use — the candidate
            # rows stand in as an M-node block, quota disabled (qid=-1;
            # admission is the node-invariant recheck below).  No third
            # copy of Filter+Score exists here.
            re_feas, re_total = step_feasible_scores(
                cand["nreq"][i] + dreq,
                cand["nest"][i] + dest,
                quse_w,
                cand["alloc"][i],
                cand["usage"][i],
                cand["fresh"][i],
                cand["ok"][i],
                req,
                sreq,
                est,
                jnp.int32(-1),
                jnp.bool_(True),
                qrt,
                qlim,
                cfg,
            )
            re_total = re_total + jnp.where(
                cand["xfeas"][i], cand["xval"][i], 0
            )
            re_feas = re_feas & cand["xfeas"][i]
            rekeys = pack_keys(re_total, re_feas, c_nodes, N)
            cur = jnp.where(dirty, rekeys, cand_key[i])  # [M]
            best_key = jnp.max(cur)
            best_node = c_nodes[jnp.argmax(cur)]
            certified = (best_key >= k_m) | sentinel_m
        feas = best_key > SENT_TH

        qblocked = (qid >= 0) & jnp.any(
            qlim[qi] & (quse_w[qi] + req > qrt[qi])
        )
        usable = pvalid_wave[i] & ~qblocked & wvalid[i]
        choice = jnp.where(feas & usable, best_node, -1)
        # a -1 outcome is exact only when it is node-INDEPENDENT
        # (quota-blocked / invalid pod / padding lane) or when
        # sentinel_m says every frozen-feasible node is already a
        # candidate (infeasible stays infeasible under commits).  With
        # k_M > sentinel, "no candidate feasible" proves nothing about
        # nodes OUTSIDE the gathered set — feasible frozen nodes below
        # k_M may remain, so the pod must end the commit prefix and
        # rerun next round against fresh state (certification via
        # sentinel_m is already in `certified`; adding ~feas here would
        # wrongly commit schedulable pods as unschedulable).
        certified = certified | ~usable

        commit = active & certified
        take_node = commit & (choice >= 0)
        choices = choices.at[i].set(jnp.where(take_node, choice, -1))
        committed = committed.at[i].set(take_node)
        done = done.at[i].set(commit)
        quse_w = jnp.where(
            take_node & (qid >= 0),
            quse_w.at[qi].add(req),
            quse_w,
        )
        ncommit = ncommit + jnp.where(commit, 1, 0)
        active = active & certified
        return (choices, committed, active, done, quse_w, ncommit)

    st0 = (
        jnp.full((W,), -1, jnp.int64),
        jnp.zeros((W,), bool),
        jnp.bool_(True),
        jnp.zeros((W,), bool),
        quse,
        jnp.int64(0),
    )
    choices, committed, _, done, quse_new, ncommit = lax.fori_loop(
        0, W, resolve, st0
    )
    return choices, committed, done, quse_new, ncommit


@devprof.boundary("solver.wave._wave_assign")
@partial(
    jax.jit,
    static_argnames=("cfg", "wave", "top_m", "has_mask", "has_scores"),
)
def _wave_assign(
    snapshot: ClusterSnapshot,
    extra_mask,
    extra_scores,
    *,
    cfg: CycleConfig,
    wave: int,
    top_m: int,
    has_mask: bool,
    has_scores: bool,
):
    """Single-chip round-based cycle: O(P / commit-prefix) sequential
    rounds instead of O(P) scan steps.

    Each round scores the next ``wave`` pods against the frozen node
    table as ONE ``[W, N]`` tensor op (vmapped ``step_feasible_scores``
    — VPU/MXU-friendly instead of ``[N]`` vector ops), freezes each
    pod's global top-``top_m`` packed keys via ``lax.top_k``, and runs
    the shared ``resolve_wave`` certification; the committed prefix
    lands on the carried node/quota state and the pointer advances by
    its length.  Bit-identical with ``greedy_assign`` on gang-free
    snapshots (same packed-key tie-break, same ElasticQuota admission
    order); parity fuzzed in tests/test_parity_fuzz.py.  It has no
    Strict gang rollback, so ``run_cycle`` serves a snapshot with gangs
    on the dense kernel or the scan.
    """
    pods, nodes, gangs, quotas = (
        snapshot.pods,
        snapshot.nodes,
        snapshot.gangs,
        snapshot.quotas,
    )
    PCAP = pods.capacity
    N = nodes.allocatable.shape[0]
    W = wave
    M = max(1, min(top_m, N))

    order = queue_order(pods.priority, pods.valid)
    order_pad = jnp.concatenate([order, jnp.zeros((W,), order.dtype)])
    score_requests = nonzero_requests(pods.requests)

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
    uprod = usage_prod if usage_prod is not None else usage_np
    is_prod_pods = pods.priority_class == int(PriorityClass.PROD)

    alloc = nodes.allocatable
    fresh = nodes.metric_fresh
    gidx = jnp.arange(N, dtype=jnp.int64)
    iota_w = jnp.arange(W)
    qrt, qlim = quotas.runtime, quotas.limited
    most_alloc = is_most_allocated(cfg)

    def one_pod_keys(nreq, nest, p):
        """Frozen [N] packed keys for pod p (quota handled in the
        resolution, so qid=-1 here)."""
        if prod_sensitive:
            ok_p = jnp.where(is_prod_pods[p], node_ok_prod, node_ok_default)
            usage_p = jnp.where(is_prod_pods[p], uprod, usage_np)
        else:
            ok_p = node_ok_default
            usage_p = usage_np
        feasible, total = step_feasible_scores(
            nreq, nest, quotas.used, alloc, usage_p, fresh, ok_p,
            pods.requests[p], score_requests[p], pods.estimated[p],
            jnp.int32(-1), pods.valid[p], qrt, qlim, cfg,
        )
        if has_mask:
            feasible = feasible & extra_mask[p]
        if has_scores:
            total = total + extra_scores[p]
        return pack_keys(total, feasible, gidx, N)

    def wave_round(carry):
        ptr, nreq, nest, quse, chosen_buf, nrounds = carry
        ps = lax.dynamic_slice(order_pad, (ptr,), (W,))
        wvalid = (ptr + iota_w) < PCAP
        # ONE [W, N] scoring op for the whole wave
        keys = jax.vmap(lambda p: one_pod_keys(nreq, nest, p))(ps)
        cand_key, lidx = lax.top_k(keys, M)  # [W, M]

        preq_wave = pods.requests[ps]
        pest_wave = pods.estimated[ps]
        psreq_wave = score_requests[ps]
        pqid_wave = pods.quota_id[ps]
        pvalid_wave = pods.valid[ps]
        pprod_wave = is_prod_pods[ps]

        if most_alloc:
            # the closed candidate universe: union of the wave's top-M
            # rows, keyed by node (duplicates harmless)
            uni = lidx.reshape(-1)  # [W*M]
            universe = dict(
                gid=uni.astype(jnp.int64),
                alloc=alloc[uni],
                nreq=nreq[uni],
                nest=nest[uni],
                usage=usage_np[uni],
                okd=node_ok_default[uni],
                fresh=fresh[uni],
                xval=(
                    extra_scores[ps[:, None], uni[None, :]]
                    if has_scores
                    else jnp.zeros((W, W * M), jnp.int64)
                ),
                xfeas=(
                    extra_mask[ps[:, None], uni[None, :]]
                    if has_mask
                    else jnp.ones((W, W * M), bool)
                ),
            )
            if prod_sensitive:
                universe["uprod"] = uprod[uni]
                universe["okp"] = node_ok_prod[uni]
            cand = None
        else:
            universe = None
            if prod_sensitive:
                usage_rows = jnp.where(
                    pprod_wave[:, None, None], uprod[lidx], usage_np[lidx]
                )
                ok_rows = jnp.where(
                    pprod_wave[:, None],
                    node_ok_prod[lidx],
                    node_ok_default[lidx],
                )
            else:
                usage_rows = usage_np[lidx]
                ok_rows = node_ok_default[lidx]
            cand = dict(
                gid=lidx.astype(jnp.int64),
                alloc=alloc[lidx],
                nreq=nreq[lidx],
                nest=nest[lidx],
                usage=usage_rows,
                ok=ok_rows,
                fresh=fresh[lidx],
                xval=(
                    extra_scores[ps[:, None], lidx]
                    if has_scores
                    else jnp.zeros((W, M), jnp.int64)
                ),
                xfeas=(
                    extra_mask[ps[:, None], lidx]
                    if has_mask
                    else jnp.ones((W, M), bool)
                ),
            )

        choices, committed, done, quse_new, ncommit = resolve_wave(
            cand_key,
            cand=cand,
            universe=universe,
            preq_wave=preq_wave,
            pest_wave=pest_wave,
            psreq_wave=psreq_wave,
            pqid_wave=pqid_wave,
            pvalid_wave=pvalid_wave,
            pprod_wave=pprod_wave,
            wvalid=wvalid,
            qrt=qrt,
            qlim=qlim,
            quse=quse,
            cfg=cfg,
            n_total=N,
            prod_sensitive=prod_sensitive,
        )

        # apply the committed prefix to the carried node state (an
        # exact scatter-add; uncommitted rows target N and drop)
        target = jnp.where(committed, choices, N)
        nreq = nreq.at[target].add(preq_wave, mode="drop")
        nest = nest.at[target].add(pest_wave, mode="drop")

        write = jnp.where(done, choices.astype(jnp.int32), jnp.int32(-1))
        # positions not committed this round keep their buffer value
        # (they will be rewritten when their round comes)
        window = lax.dynamic_slice(chosen_buf, (ptr,), (W,))
        window = jnp.where(done, write, window)
        chosen_buf = lax.dynamic_update_slice(chosen_buf, window, (ptr,))

        return (ptr + ncommit, nreq, nest, quse_new, chosen_buf, nrounds + 1)

    def cond(carry):
        return carry[0] < PCAP

    init = (
        jnp.int64(0),
        nodes.requested,
        jnp.zeros_like(nodes.requested),
        quotas.used,
        jnp.full((PCAP + W,), -1, jnp.int32),
        jnp.int64(0),
    )
    _, node_requested, node_estimated, quota_used, chosen_buf, nrounds = (
        lax.while_loop(cond, wave_round, init)
    )

    assignment = (
        jnp.full((PCAP,), -1, jnp.int32).at[order].set(chosen_buf[:PCAP])
    )
    # no Strict gang rollback in this engine (run_cycle and the bridge
    # send it no gang): a gang short of minMember only WAITs here
    status = jnp.where(assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE)
    assigned = (assignment >= 0) & pods.valid
    _, pod_gang_ok = gang_satisfaction(
        assignment, pods.valid, pods.gang_id, gangs.min_member
    )
    status = jnp.where(assigned & ~pod_gang_ok, STATUS_WAIT_GANG, status)
    return CycleResult(
        assignment=assignment,
        status=status.astype(jnp.int32),
        node_requested=node_requested,
        node_estimated=node_estimated,
        quota_used=quota_used,
        rounds=nrounds,
        path="wave",
    )


def wave_assign(
    snapshot: ClusterSnapshot,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    extra_mask: Optional[jnp.ndarray] = None,
    extra_scores: Optional[jnp.ndarray] = None,
    wave: Optional[int] = None,
    top_m: Optional[int] = None,
    scores_hi: Optional[int] = None,
) -> CycleResult:
    """Wave-batched drop-in for ``greedy_assign``: bit-identical
    placements, ~W pods committed per sequential round.

    ``wave``/``top_m`` default from the ``CycleConfig`` knobs; both are
    STATIC jit arguments (a traced wave width would retrace every cycle
    — the koordlint retrace-hazard rule enforces this at every jit
    boundary).  Returns a ``CycleResult`` with ``rounds`` set to the
    number of sequential wave rounds and ``path="wave"``.

    ``scores_hi``: callers that already reduced ``extra_scores`` to its
    max magnitude (the run_cycle dispatcher does, for its kernel bound)
    pass it to skip a second blocking device->host reduction per cycle
    — the ``i32_ok`` pattern.
    """
    W = int(cfg.wave if wave is None else wave)
    M = int(cfg.top_m if top_m is None else top_m)
    if W < 1 or M < 1:
        raise ValueError(f"wave ({W}) and top_m ({M}) must be >= 1")
    if extra_scores is not None:
        # the packed key multiplies scores by N; plugin scores are tiny
        # by construction, but extra_scores is caller-supplied — values
        # at the sentinel's magnitude would decode as infeasible (or
        # overflow the key), silently breaking parity
        hi = (
            int(jnp.max(jnp.abs(extra_scores)))
            if scores_hi is None
            else int(scores_hi)
        )
        if hi >= 2**31:
            raise ValueError(
                f"extra_scores magnitude {hi} too large for the packed "
                "key (must be < 2^31); use solver.greedy_assign"
            )
    return _wave_assign(
        snapshot,
        extra_mask,
        extra_scores,
        cfg=cfg,
        wave=W,
        top_m=M,
        has_mask=extra_mask is not None,
        has_scores=extra_scores is not None,
    )
