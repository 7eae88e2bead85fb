"""Explicitly-scheduled multi-chip greedy assignment (shard_map).

``greedy_assign`` (solver/greedy.py) is a sequential scan over pods; under
plain GSPMD sharding every scan step's argmax-over-nodes and node-state
update make the compiler infer cross-device communication, which scales
badly with step count.  This module instead partitions the scan body by
hand with ``jax.shard_map``:

* node state (allocatable / usage / requested / estimated) is sharded
  across ALL mesh devices along the node axis — the cluster spreads over
  the combined HBM;
* pod rows and the quota table are replicated (quota updates are computed
  identically on every device);
* each scan step does local Filter+Score on its node shard, then exactly
  ONE collective — a ``lax.pmax`` of a packed (score, node-index) key —
  to agree on the winning node, then a local masked update on the owning
  shard.

The packed key encodes ``score * N_total + (N_total-1 - node_index)`` so a
single max picks the highest score with the LOWEST node index — the same
tie-break as ``jnp.argmax`` in the scan path, giving bit-identical
placements (tests/test_parallel.py asserts parity vs greedy_assign).

Reference analog: the Score fan-out at
``pkg/scheduler/frameworkext/framework_extender.go:216`` parallelizes one
pod's scoring over 16 goroutines; here the whole cycle's node dimension is
parallelized over the device mesh with one ICI collective per pod.
"""

from __future__ import annotations

import dataclasses as dc
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from koordinator_tpu.config import (
    CycleConfig,
    DEFAULT_CYCLE_CONFIG,
)
from koordinator_tpu.constraints.gang import gang_satisfaction
from koordinator_tpu.model.snapshot import ClusterSnapshot
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
    step_feasible_scores,
)

# the packed-key encode/decode, the cross-shard top-M merge and the
# in-wave certification (solver/wave.py)
from koordinator_tpu.solver.wave import (
    is_most_allocated,
    merge_topm,
    merge_topm_keys,
    pack_keys,
    decode_key,
    resolve_wave,
    score_feasible,
)


# the round-based cycle's width: pods scored per round (measured at the
# 10k x 2k benchmark snapshot, see _assign_waves); the served mesh path
# runs it and labels its telemetry with it
WAVE_WIDTH = 32


def _pad_nodes_to(snap: ClusterSnapshot, multiple: int) -> ClusterSnapshot:
    """Pad the node axis to a multiple of the device count with invalid
    rows (valid=False keeps them unchoosable)."""
    nodes = snap.nodes
    N = nodes.allocatable.shape[0]
    pad = (-N) % multiple
    if pad == 0:
        return snap
    pad2 = lambda a: jnp.pad(a, ((0, pad), (0, 0)))
    pad1 = lambda a: jnp.pad(a, (0, pad))
    return dc.replace(
        snap,
        nodes=dc.replace(
            nodes,
            allocatable=pad2(nodes.allocatable),
            requested=pad2(nodes.requested),
            usage=pad2(nodes.usage),
            metric_fresh=pad1(nodes.metric_fresh),
            valid=pad1(nodes.valid),
        ),
    )


def _cycle_operands(
    snapshot, cfg, ax, order_operand, extra_mask, extra_scores,
    has_mask, has_scores,
):
    """Shared shard_map prologue for both sharded entry points (per-pod
    and wave): LoadAware masks + score-usage selection
    (load_aware.go:150-226,291-311, node-local so computed host-side and
    sharded with the node axis), the operand list, and partition specs.
    Returns (operands, in_specs, prod_sensitive)."""
    pods, nodes, quotas = snapshot.pods, snapshot.nodes, snapshot.quotas
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
    if usage_prod is None:
        usage_prod = usage_np
    is_prod_pods = pods.priority_class == int(PriorityClass.PROD)

    node_spec = P(ax, None)
    flag_spec = P(ax)
    rep = P()
    pn_spec = P(None, ax)  # [P, N] extended-plugin tensors: shard nodes

    operands = [
        nodes.allocatable,
        nodes.requested,
        usage_np,
        usage_prod,
        node_ok_default,
        node_ok_prod,
        nodes.metric_fresh,
        order_operand,
        pods.requests,
        score_requests,
        pods.estimated,
        pods.quota_id,
        pods.valid,
        is_prod_pods,
        quotas.runtime,
        quotas.limited,
        quotas.used,
    ]
    in_specs = [
        node_spec, node_spec, node_spec, node_spec, flag_spec, flag_spec,
        flag_spec, rep, rep, rep, rep, rep, rep, rep, rep, rep, rep,
    ]
    if has_mask:
        operands.append(extra_mask)
        in_specs.append(pn_spec)
    if has_scores:
        operands.append(extra_scores)
        in_specs.append(pn_spec)
    return operands, in_specs, prod_sensitive


@devprof.boundary("parallel.shard_assign._assign_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh", "has_mask", "has_scores"))
def _assign_sharded(
    snapshot: ClusterSnapshot,
    extra_mask,
    extra_scores,
    *,
    cfg: CycleConfig,
    mesh: Mesh,
    has_mask: bool,
    has_scores: bool,
):
    pods, nodes, quotas = snapshot.pods, snapshot.nodes, snapshot.quotas
    N = nodes.allocatable.shape[0]
    axes = tuple(mesh.axis_names)
    ax = axes if len(axes) > 1 else axes[0]

    order = queue_order(pods.priority, pods.valid)
    operands, in_specs, prod_sensitive = _cycle_operands(
        snapshot, cfg, ax, order, extra_mask, extra_scores,
        has_mask, has_scores,
    )
    node_spec = P(ax, None)
    rep = P()

    def body(
        alloc, req0, usage, uprod, node_ok_def, node_ok_pr, fresh,
        order, preq, psreq, pest, pqid, pvalid, pprod, qrt, qlim, quse0,
        *extras,
    ):
        xmask = extras[0] if has_mask else None
        xscores = extras[-1] if has_scores else None
        n_loc = alloc.shape[0]
        offset = lax.axis_index(ax).astype(jnp.int64) * n_loc
        gidx = offset + jnp.arange(n_loc, dtype=jnp.int64)

        def step(state, p):
            node_requested, node_estimated, quota_used = state
            req = preq[p]
            est = pest[p]
            qid = pqid[p]
            q = jnp.maximum(qid, 0)
            if prod_sensitive:
                node_ok_p = jnp.where(pprod[p], node_ok_pr, node_ok_def)
                usage_p = jnp.where(pprod[p], uprod, usage)
            else:
                node_ok_p = node_ok_def
                usage_p = usage

            # same step semantics as greedy_assign, on the local node shard
            feasible, total = step_feasible_scores(
                node_requested,
                node_estimated,
                quota_used,
                alloc,
                usage_p,
                fresh,
                node_ok_p,
                req,
                psreq[p],
                est,
                qid,
                pvalid[p],
                qrt,
                qlim,
                cfg,
            )
            if xmask is not None:
                feasible = feasible & xmask[p]
            if xscores is not None:
                total = total + xscores[p]

            # ONE collective per step: packed (score, lowest-index) max
            key = pack_keys(total, feasible, gidx, N)
            gkey = lax.pmax(jnp.max(key), ax)
            best_score, chosen = decode_key(gkey, N)
            any_feasible = score_feasible(best_score)
            chosen = jnp.where(any_feasible, chosen, -1)

            local = chosen - offset.astype(jnp.int32)
            hit = (local >= 0) & (local < n_loc) & any_feasible
            onehot = (jnp.arange(n_loc) == local) & hit
            node_requested = node_requested + jnp.where(
                onehot[:, None], req[None, :], 0
            )
            node_estimated = node_estimated + jnp.where(
                onehot[:, None], est[None, :], 0
            )
            quota_used = jnp.where(
                any_feasible & (qid >= 0), quota_used.at[q].add(req), quota_used
            )
            return (node_requested, node_estimated, quota_used), chosen

        init = (req0, jnp.zeros_like(req0), quse0)
        (nreq, nest, quse), chosen_in_order = lax.scan(step, init, order)
        return chosen_in_order, nreq, nest, quse

    chosen_in_order, node_requested, node_estimated, quota_used = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(rep, node_spec, node_spec, rep),
        check_vma=False,
    )(*operands)

    Pcap = pods.capacity
    assignment = jnp.full((Pcap,), -1, jnp.int32).at[order].set(chosen_in_order)
    # no Strict gang rollback in this engine (run_cycle and the bridge
    # send it no gang): a gang short of minMember only WAITs here
    status = jnp.where(assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE)
    assigned = (assignment >= 0) & pods.valid
    _, pod_gang_ok = gang_satisfaction(
        assignment, pods.valid, pods.gang_id, snapshot.gangs.min_member
    )
    status = jnp.where(assigned & ~pod_gang_ok, STATUS_WAIT_GANG, status)
    return CycleResult(
        assignment=assignment,
        status=status.astype(jnp.int32),
        node_requested=node_requested,
        node_estimated=node_estimated,
        quota_used=quota_used,
        path="shard",
    )


@devprof.boundary("parallel.shard_assign._assign_waves")
@partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "has_mask", "has_scores", "wave", "top_m"),
)
def _assign_waves(
    snapshot: ClusterSnapshot,
    extra_mask,
    extra_scores,
    *,
    cfg: CycleConfig,
    mesh: Mesh,
    has_mask: bool,
    has_scores: bool,
    wave: int,
    top_m: int,
):
    """Round-based sharded cycle: O(P/prefix) collectives instead of O(P).

    Each round, every shard scores the next ``wave`` pods against its
    frozen node shard and contributes its local top-``top_m`` candidates
    (packed key + the candidate's node-state rows) to ONE ``all_gather``.
    Every device then runs the same deterministic in-wave resolution:

    * quota admission is node-invariant, so it is rechecked exactly
      against the replicated in-wave quota state (a blocked pod commits
      as unschedulable, never needing a rescan);
    * a pod whose candidate node was committed-to earlier in the wave has
      that candidate's key recomputed from the gathered state rows plus
      the in-wave delta (commit targets and pod vectors are replicated,
      so every device derives the identical key);
    * scores only decrease as load is added (LeastAllocated), and packed
      keys are unique, so any node outside the pod's top-``top_m``
      candidates stays strictly below the frozen ``top_m``-th key k_M —
      a pod's choice is therefore EXACT (bit-identical with the
      sequential scan) whenever its best current candidate key is still
      >= k_M.  The first pod in the wave that cannot be certified ends
      the commit prefix; it and everything after rerun next round
      against fresh state;
    * under ``MostAllocated`` (round-4 review #5) scores INCREASE as
      load is added, so the k_M lower-bound argument inverts.  The exact
      symmetric certificate rides the CLOSED candidate universe: every
      in-wave commit lands on some wave pod's gathered candidate, so the
      union of all wave pods' per-shard top-M candidates (whose full
      state rows ride the same all_gather) is the ONLY set of nodes
      whose keys can move within the round.  Each pod re-keys that
      whole universe exactly (frozen rows + the replicated in-wave
      commit deltas) and certifies when the universe best >= its own
      frozen global k_M: any node outside the pod's frozen top-M has
      frozen key <= k_M and — receiving no in-wave commits — can never
      rise above it, while packed-key uniqueness turns the boundary
      case into membership.  Pod 0 of each round has no earlier in-wave
      commits, so its frozen keys ARE current and it always commits —
      liveness is unchanged.

    Measured on the 10k x 2k benchmark snapshot: wave=32/top_m=4 commits
    ~20 pods per collective (500 rounds vs 10,000 per-pod collectives).

    Reference analog: the per-pod Score fan-out bounded by 16 goroutines
    (``frameworkext/framework_extender.go:216``); here the fan-out is the
    device mesh and the round batching bounds the collective count.
    """
    pods, nodes, quotas = snapshot.pods, snapshot.nodes, snapshot.quotas
    N = nodes.allocatable.shape[0]
    PCAP = pods.capacity
    W = wave
    # the local top-M runs on each shard's node slice, so M is bounded by
    # the PER-SHARD node count (a 16-node cluster over 8 shards has 2-node
    # slices; fuzz-found)
    M = max(1, min(top_m, N // mesh.size))
    axes = tuple(mesh.axis_names)
    ax = axes if len(axes) > 1 else axes[0]

    order = queue_order(pods.priority, pods.valid)
    order_pad = jnp.concatenate([order, jnp.zeros((W,), order.dtype)])
    operands, in_specs, prod_sensitive = _cycle_operands(
        snapshot, cfg, ax, order_pad, extra_mask, extra_scores,
        has_mask, has_scores,
    )
    node_spec = P(ax, None)
    rep = P()

    # MostAllocated needs the upper-bound certificate (docstring bullet 4)
    most_alloc = is_most_allocated(cfg)

    def body(
        alloc, req0, usage, uprod, node_ok_def, node_ok_pr, fresh,
        order_pad, preq, psreq, pest, pqid, pvalid, pprod, qrt, qlim, quse0,
        *extras,
    ):
        xmask = extras[0] if has_mask else None
        xscores = extras[-1] if has_scores else None
        n_loc = alloc.shape[0]
        offset = lax.axis_index(ax).astype(jnp.int64) * n_loc
        gidx = offset + jnp.arange(n_loc, dtype=jnp.int64)
        iota_w = jnp.arange(W)

        def one_pod_keys(nreq, nest, p):
            """Frozen [n_loc] packed keys for pod p (quota handled in the
            replicated resolution, so qid=-1 here)."""
            if prod_sensitive:
                ok_p = jnp.where(pprod[p], node_ok_pr, node_ok_def)
                usage_p = jnp.where(pprod[p], uprod, usage)
            else:
                ok_p = node_ok_def
                usage_p = usage
            feasible, total = step_feasible_scores(
                nreq, nest, quse0, alloc, usage_p, fresh, ok_p,
                preq[p], psreq[p], pest[p], jnp.int32(-1), pvalid[p],
                qrt, qlim, cfg,
            )
            if xmask is not None:
                feasible = feasible & xmask[p]
            if xscores is not None:
                total = total + xscores[p]
            return pack_keys(total, feasible, gidx, N)

        def wave_round(carry):
            ptr, nreq, nest, quse, chosen_buf, nwaves = carry
            ps = lax.dynamic_slice(order_pad, (ptr,), (W,))
            wvalid = (ptr + iota_w) < PCAP
            preq_wave = preq[ps]  # [W, R]
            pest_wave = pest[ps]

            keys_loc = jax.vmap(lambda p: one_pod_keys(nreq, nest, p))(ps)
            lvals, lidx = lax.top_k(keys_loc, M)  # [W, M]
            gid = offset + lidx.astype(jnp.int64)

            if most_alloc:
                # the closed candidate universe (see docstring): this
                # shard's contribution is the union of its W pods' local
                # top-M rows, keyed by NODE (duplicates are harmless —
                # identical rows produce identical keys)
                uni_idx = lidx.reshape(-1)  # [W*M] local slots
                uni_gid = offset + uni_idx.astype(jnp.int64)

            if most_alloc:
                # universe payload: node-keyed rows for the closed
                # candidate set + the frozen per-pod keys (k_M only)
                payload = dict(
                    key=lvals,  # [W, M]
                    u_gid=uni_gid,  # [W*M]
                    u_alloc=alloc[uni_idx],
                    u_nreq=nreq[uni_idx],
                    u_nest=nest[uni_idx],
                    u_usage=usage[uni_idx],
                    u_okd=node_ok_def[uni_idx],
                    u_fresh=fresh[uni_idx],
                    u_xval=(
                        xscores[ps[:, None], uni_idx[None, :]]
                        if xscores is not None
                        else jnp.zeros((W, W * M), jnp.int64)
                    ),
                    u_xfeas=(
                        xmask[ps[:, None], uni_idx[None, :]]
                        if xmask is not None
                        else jnp.ones((W, W * M), bool)
                    ),
                )
                if prod_sensitive:
                    # the prod-usage variants ride only when some pod can
                    # actually select them (trace-time flag) — otherwise
                    # they would double the universe rows in the ONE
                    # collective this design exists to minimize
                    payload["u_uprod"] = uprod[uni_idx]
                    payload["u_okp"] = node_ok_pr[uni_idx]
            else:
                if prod_sensitive:
                    usage_rows = jnp.where(
                        pprod[ps][:, None, None], uprod[lidx], usage[lidx]
                    )
                    ok_rows = jnp.where(
                        pprod[ps][:, None], node_ok_pr[lidx], node_ok_def[lidx]
                    )
                else:
                    usage_rows = usage[lidx]
                    ok_rows = node_ok_def[lidx]
                payload = dict(
                    key=lvals,
                    gid=gid,
                    alloc=alloc[lidx],
                    nreq=nreq[lidx],
                    nest=nest[lidx],
                    usage=usage_rows,
                    ok=ok_rows,
                    fresh=fresh[lidx],
                    xval=(
                        xscores[ps[:, None], lidx]
                        if xscores is not None
                        else jnp.zeros((W, M), jnp.int64)
                    ),
                    xfeas=(
                        xmask[ps[:, None], lidx]
                        if xmask is not None
                        else jnp.ones((W, M), bool)
                    ),
                )
            # the ONE collective of the round
            gathered = lax.all_gather(payload, ax)  # leading [S, ...]

            if most_alloc:
                # frozen per-pod global top-M keys (k_M certification
                # bar), via the shared cross-shard merge
                cand_key = merge_topm_keys(gathered["key"], M)
                R_ = alloc.shape[1]
                u_gid = gathered["u_gid"].reshape(-1)  # [U = S*W*M]
                U = u_gid.shape[0]
                universe = dict(
                    gid=u_gid,
                    alloc=gathered["u_alloc"].reshape(U, R_),
                    nreq=gathered["u_nreq"].reshape(U, R_),
                    nest=gathered["u_nest"].reshape(U, R_),
                    usage=gathered["u_usage"].reshape(U, R_),
                    okd=gathered["u_okd"].reshape(U),
                    fresh=gathered["u_fresh"].reshape(U),
                    # [S, W, W*M] -> [W, U] aligned with u_gid's (s, k)
                    # order
                    xval=jnp.moveaxis(
                        gathered["u_xval"], 0, 1
                    ).reshape(W, U),
                    xfeas=jnp.moveaxis(
                        gathered["u_xfeas"], 0, 1
                    ).reshape(W, U),
                )
                if prod_sensitive:
                    universe["uprod"] = gathered["u_uprod"].reshape(U, R_)
                    universe["okp"] = gathered["u_okp"].reshape(U)
                cand = None
            else:
                # the shared cross-shard top-M merge (solver/wave.py):
                # global candidates + their state rows, [W, M]
                cand_key, cand = merge_topm(gathered, M)
                universe = None

            # the SHARED certification resolver (solver/wave.py): commit
            # targets and pod vectors are replicated, so every device
            # derives the identical prefix
            choices, committed, done, quse_new, ncommit = resolve_wave(
                cand_key,
                cand=cand,
                universe=universe,
                preq_wave=preq_wave,
                pest_wave=pest_wave,
                psreq_wave=psreq[ps],
                pqid_wave=pqid[ps],
                pvalid_wave=pvalid[ps],
                pprod_wave=pprod[ps],
                wvalid=wvalid,
                qrt=qrt,
                qlim=qlim,
                quse=quse,
                cfg=cfg,
                n_total=N,
                prod_sensitive=prod_sensitive,
            )

            # apply the committed prefix to the local shard state
            local = choices - offset
            mine = committed & (local >= 0) & (local < n_loc)
            target = jnp.where(mine, local, n_loc)  # not mine: dropped
            nreq = nreq.at[target].add(preq_wave, mode="drop")
            nest = nest.at[target].add(pest_wave, mode="drop")

            write = jnp.where(
                done, choices.astype(jnp.int32), jnp.int32(-1)
            )
            # positions not committed this round keep their buffer value
            # (they will be rewritten when their round comes)
            window = lax.dynamic_slice(chosen_buf, (ptr,), (W,))
            window = jnp.where(done, write, window)
            chosen_buf = lax.dynamic_update_slice(chosen_buf, window, (ptr,))

            ptr = ptr + ncommit
            return (ptr, nreq, nest, quse_new, chosen_buf, nwaves + 1)

        def cond(carry):
            return carry[0] < PCAP

        init = (
            jnp.int64(0),
            req0,
            jnp.zeros_like(req0),
            quse0,
            jnp.full((PCAP + W,), -1, jnp.int32),
            jnp.int64(0),
        )
        ptr, nreq, nest, quse, chosen_buf, nwaves = lax.while_loop(
            cond, wave_round, init
        )
        return chosen_buf[:PCAP], nreq, nest, quse, nwaves

    (chosen_in_order, node_requested, node_estimated, quota_used, nwaves) = (
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(rep, node_spec, node_spec, rep, rep),
            check_vma=False,
        )(*operands)
    )

    Pcap = pods.capacity
    assignment = jnp.full((Pcap,), -1, jnp.int32).at[order].set(chosen_in_order)
    # no Strict gang rollback in this engine (run_cycle and the bridge
    # send it no gang): a gang short of minMember only WAITs here
    status = jnp.where(assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE)
    assigned = (assignment >= 0) & pods.valid
    _, pod_gang_ok = gang_satisfaction(
        assignment, pods.valid, pods.gang_id, snapshot.gangs.min_member
    )
    status = jnp.where(assigned & ~pod_gang_ok, STATUS_WAIT_GANG, status)
    return (
        CycleResult(
            assignment=assignment,
            status=status.astype(jnp.int32),
            node_requested=node_requested,
            node_estimated=node_estimated,
            quota_used=quota_used,
            rounds=nwaves,
            path="shard",
        ),
        nwaves,
    )


def greedy_assign_waves(
    snapshot: ClusterSnapshot,
    mesh: Mesh,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    extra_mask: Optional[jnp.ndarray] = None,
    extra_scores: Optional[jnp.ndarray] = None,
    wave: int = WAVE_WIDTH,
    top_m: int = 4,
    spans=None,
    candidates: Optional[jnp.ndarray] = None,
):
    """Round-based sharded assignment (see _assign_waves): bit-identical
    with greedy_assign, one all_gather per round instead of one pmax per
    pod.  Returns (CycleResult, collective_round_count).

    ``candidates``: an optional [P, C] sparse candidate-index map
    (ISSUE 16, solver/candidates.py — ascending real node ids, pad
    slots >= N).  Expanded host-side into a [P, N] membership mask and
    ANDed into ``extra_mask`` BEFORE the node padding, so the wave
    rounds only ever pick a pod's candidate nodes while the
    cross-shard gang/quota reduction rides the existing top-M merge
    unchanged — no new traced parameters, no new compiled shapes.
    Exact whenever the lists are non-overflowed (every feasible node
    is a member; see ``check_candidate_overflow``).

    Both fit strategies certify exactly: LeastAllocated through the
    frozen k_M lower bound (scores non-increasing in committed load),
    MostAllocated through the symmetric frozen upper bound on
    non-candidate nodes (round-4 review #5; see the _assign_waves
    docstring).  The reference parallelizes Score identically for both
    (``frameworkext/framework_extender.go:216``,
    ``plugins/nodenumaresource/most_allocated.go``).

    ``spans``: optional ``obs.spans.SpanRecorder``.  Only the HOST-side
    stages are timed (pad/prep vs the sharded rounds' dispatch) — the
    recorder never enters ``_assign_waves``' traced body, so the spans
    add no host syncs and no retraces; round counts come from the
    result the device already returns."""
    if extra_scores is not None:
        hi = int(jnp.max(jnp.abs(extra_scores)))
        if hi >= 2**31:
            raise ValueError(
                f"extra_scores magnitude {hi} too large for the packed-key "
                "collective (must be < 2^31); use solver.greedy_assign"
            )
    from koordinator_tpu.obs.spans import maybe_span

    with maybe_span(spans, "shard_prep"):
        n_dev = mesh.size
        orig_n = snapshot.nodes.allocatable.shape[0]
        if candidates is not None:
            from koordinator_tpu.solver.candidates import (
                candidate_membership_mask,
            )

            member = candidate_membership_mask(candidates, orig_n)
            extra_mask = (
                member if extra_mask is None else extra_mask & member
            )
        snapshot = _pad_nodes_to(snapshot, n_dev)
        padded_n = snapshot.nodes.allocatable.shape[0]
        if extra_mask is not None and extra_mask.shape[1] != padded_n:
            extra_mask = jnp.pad(
                extra_mask, ((0, 0), (0, padded_n - extra_mask.shape[1]))
            )
        if extra_scores is not None and extra_scores.shape[1] != padded_n:
            extra_scores = jnp.pad(
                extra_scores, ((0, 0), (0, padded_n - extra_scores.shape[1]))
            )
    with maybe_span(spans, "shard_rounds"):
        result, nwaves = _assign_waves(
            snapshot,
            extra_mask,
            extra_scores,
            cfg=cfg,
            mesh=mesh,
            has_mask=extra_mask is not None,
            has_scores=extra_scores is not None,
            wave=wave,
            top_m=top_m,
        )
    if result.node_requested.shape[0] != orig_n:
        result = dc.replace(
            result,
            node_requested=result.node_requested[:orig_n],
            node_estimated=result.node_estimated[:orig_n],
        )
    return result, int(nwaves)


def greedy_assign_sharded(
    snapshot: ClusterSnapshot,
    mesh: Mesh,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    extra_mask: Optional[jnp.ndarray] = None,
    extra_scores: Optional[jnp.ndarray] = None,
) -> CycleResult:
    """Sequential-parity greedy assignment with node state sharded over
    every device of ``mesh`` and one collective per pod step.

    Placements are bit-identical with solver.greedy.greedy_assign;
    ``node_requested``/``node_estimated`` come back node-sharded over the
    mesh and trimmed to the snapshot's node bucket.
    """
    if extra_scores is not None:
        # the packed key multiplies scores by N; plugin scores are tiny by
        # construction, but extra_scores is caller-supplied — values at the
        # sentinel's magnitude would decode as infeasible (or overflow the
        # key), silently breaking parity, so reject them loudly instead
        hi = int(jnp.max(jnp.abs(extra_scores)))
        if hi >= 2**31:
            raise ValueError(
                f"extra_scores magnitude {hi} too large for the packed-key "
                "collective (must be < 2^31); use solver.greedy_assign"
            )
    n_dev = mesh.size
    orig_n = snapshot.nodes.allocatable.shape[0]
    snapshot = _pad_nodes_to(snapshot, n_dev)
    padded_n = snapshot.nodes.allocatable.shape[0]
    if extra_mask is not None and extra_mask.shape[1] != padded_n:
        extra_mask = jnp.pad(
            extra_mask, ((0, 0), (0, padded_n - extra_mask.shape[1]))
        )
    if extra_scores is not None and extra_scores.shape[1] != padded_n:
        extra_scores = jnp.pad(
            extra_scores, ((0, 0), (0, padded_n - extra_scores.shape[1]))
        )
    result = _assign_sharded(
        snapshot,
        extra_mask,
        extra_scores,
        cfg=cfg,
        mesh=mesh,
        has_mask=extra_mask is not None,
        has_scores=extra_scores is not None,
    )
    if result.node_requested.shape[0] != orig_n:
        result = CycleResult(
            assignment=result.assignment,
            status=result.status,
            node_requested=result.node_requested[:orig_n],
            node_estimated=result.node_estimated[:orig_n],
            quota_used=result.quota_used,
            path=result.path,
        )
    return result
