"""Wave/top-M certification: the resolution math of the multi-chip
round-based cycle (``parallel/shard_assign.py greedy_assign_waves``).

``greedy_assign`` replays the reference's one-pod-at-a-time cycle, so the
round-based cycle must prove each pod's choice equals what the sequential
scan would have picked.  Its primitives:

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
docs/KERNEL.md "The round-based multi-chip cycle"):

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

tests/test_parity_fuzz.py and tests/test_parallel.py hold the round-based
cycle bit-identical to the scan.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from koordinator_tpu.config import CycleConfig, MOST_ALLOCATED
from koordinator_tpu.solver.greedy import step_feasible_scores

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
