"""Sparse candidate-set scoring: [P, C] instead of [P, N] (ISSUE 16).

Every dense engine — ``score_cycle``, the incremental rescore, the
sharded rescore — materializes the full pods x nodes tensor, which at
true production scale (1M pods x 100k nodes) no chip can hold.  Real
schedulers never score every node (upstream K8s samples via
``percentageOfNodesToScore``; the constraint-packing term's source
scores feasibility-filtered subsets), so this module serves Score from
a per-pod CANDIDATE LIST instead:

* **feasibility pre-mask** — ``solver/greedy.py feasibility_mask``
  (the ``score_all`` mask half factored standalone: requests-fit +
  node validity + loadaware freshness/thresholds + term masks) is
  swept over the node axis in power-of-two BLOCKS, so the only dense
  tensor ever materialized is [P, B] for one block — never [P, N].
* **candidate gather** — each pod keeps the C LOWEST-INDEXED feasible
  nodes (C = ``cfg.candidate_width``, a power of two, static in every
  jit signature; pad slots carry the sentinel N).  Lists are
  ascending, which makes index-map-back preserve ``lax.top_k``'s
  lower-index tie-break exactly.
* **sparse scoring** — the existing cellwise ``score_all`` body (fit +
  loadaware + the full term stack) evaluated over the gathered [P, C]
  cells via a vmap of per-pod sub-snapshots; ``sparse_top_k`` maps
  winners back through the index map to real node ids.

Exactness contract (the reason top-C-by-INDEX, not top-C-by-score): a
candidate list is exact only if it contains EVERY feasible node for
its pod.  ``count`` tracks the true per-pod feasible total; whenever
``count > C`` for any pod the engine must raise
:class:`CandidateOverflow` — REFUSE rather than silently serve a
truncated node set (the brownout path's refusal precedent).  Under
that invariant the sparse reply is byte-identical to the dense
engine's: same feasible set, same scores (the cellwise term contract),
same tie-breaks (ascending lists).  A score-ranked top-C could not
offer this: a low-ranked node can enter the true top-k after a delta
without ever being in the list — silent wrongness, the one thing the
engine ladder never does.

Dirty attribution (bridge/state.py ``CandidateResidency``): a dirty
node invalidates only the candidate lists containing it —
``refresh_candidates`` evicts the dirty nodes from every list,
re-evaluates just their feasibility columns, and sort-merges them
back; dirty pod rows rebuild from scratch.  Counts stay exact through
the merge, so overflow detection survives any delta stream.  Dirty
index vectors ride the same power-of-two pad buckets as the
incremental rescore (``_pad_rows``) — the dirty COUNT never crosses a
jit boundary, and neither does C (koordlint retrace-hazard shape 6
rejects traced candidate widths statically).

Pod-axis sharding (parallel/mesh.py ``POD_AXIS``): the [P, C] tensors
split over pod rows, node tables replicate, and the build / refresh /
score kernels run as ``shard_map`` bodies with zero collectives — the
sparse engine's scale axis is pods, the transpose of the dense
residency's ``P(None, "nodes")``.
"""

from __future__ import annotations

import dataclasses
import os
from collections import deque
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from koordinator_tpu.obs import devprof
from koordinator_tpu.solver.greedy import feasibility_mask, score_all
from koordinator_tpu.solver.incremental import (
    _pad_rows,
    _take_nodes,
    _take_pods,
)

# Node-axis sweep width of the blocked feasibility pass.  Powers of two
# only: node buckets are powers of two, so any power-of-two block <= N
# divides the axis exactly and the scan length is static per geometry.
_SWEEP_BLOCK = 1024

# Cold-build routing (ISSUE 20): below this many blocks the serial
# lax.scan build wins (one dispatch, no host round-trips); at or above
# it the pipelined build overlaps device feasibility compute with host
# merges and prunes blocks that cannot reach any pod's C-prefix.
# KOORD_PARALLEL_BUILD=0 forces serial, =1 forces pipelined, anything
# else (the default, "auto") applies this threshold.
_PARALLEL_MIN_BLOCKS = 16

# extract launches kept in flight ahead of the host merge: enough to
# hide one merge behind device compute, small enough that a refused
# build (overflow raises at readback) never queued the whole sweep
_PIPELINE_DEPTH = 4


class CandidateOverflow(RuntimeError):
    """A pod's feasible-node count exceeds the candidate width C.

    The exactness contract requires every feasible node to be IN its
    pod's candidate list; a list that cannot hold them all would serve
    a silently truncated node set, so the engine refuses instead
    (servers map this to a clean RPC error advising a wider
    ``--candidate-width``)."""

    def __init__(self, width: int, max_feasible: int, pods: int):
        self.width = int(width)
        self.max_feasible = int(max_feasible)
        self.pods = int(pods)
        super().__init__(
            f"sparse candidate width {self.width} cannot hold every "
            f"feasible node: {self.pods} pod(s) have up to "
            f"{self.max_feasible} feasible nodes; raise "
            "--candidate-width (power of two) — the sparse engine "
            "refuses rather than silently degrade to a truncated "
            "candidate set"
        )


def check_candidate_overflow(count, width: int) -> None:
    """Raise :class:`CandidateOverflow` if any pod's exact feasible
    count exceeds ``width``.  ``count`` is the host readback of a
    build/refresh ``count`` vector — callers fold it into the one
    stacked ``device_get`` they already pay."""
    count = np.asarray(count)
    over = count > int(width)
    if bool(np.any(over)):
        raise CandidateOverflow(
            width, int(count.max()), int(np.count_nonzero(over))
        )


def _sweep_block(n: int, c: int) -> int:
    return min(int(n), max(int(c), _SWEEP_BLOCK))


def _merge_lowest(cand: jnp.ndarray, new_idx: jnp.ndarray) -> jnp.ndarray:
    """Keep the C lowest node indices of ``cand ∪ new_idx`` (both carry
    the sentinel N in empty slots; sort pushes sentinels past every
    real index, so the C-prefix is the merged ascending list)."""
    C = cand.shape[1]
    merged = jnp.sort(jnp.concatenate([cand, new_idx], axis=1), axis=1)
    return merged[:, :C]


def _build_carry(snapshot, cfg):
    """Blocked feasibility sweep over the whole node axis ->
    (cand i32[P, C] ascending with sentinel N, count i64[P] exact).
    Never materializes more than [P, B] feasibility bits at once."""
    nodes, pods = snapshot.nodes, snapshot.pods
    n = nodes.allocatable.shape[0]
    p = pods.requests.shape[0]
    c = int(cfg.candidate_width)
    b = _sweep_block(n, c)

    def step(carry, block):
        cand, count = carry
        gidx = block * b + jnp.arange(b, dtype=jnp.int32)
        sub = dataclasses.replace(snapshot, nodes=_take_nodes(nodes, gidx))
        feas = feasibility_mask(sub, cfg)  # [P, B]
        count = count + jnp.sum(feas, axis=-1, dtype=jnp.int64)
        new_idx = jnp.where(feas, gidx[None, :], jnp.int32(n))
        return (_merge_lowest(cand, new_idx), count), None

    init = (
        jnp.full((p, c), n, jnp.int32),
        jnp.zeros((p,), jnp.int64),
    )
    (cand, count), _ = lax.scan(
        step, init, jnp.arange(n // b, dtype=jnp.int32)
    )
    return cand, count


@devprof.boundary("solver.candidates._build")
@partial(jax.jit, static_argnames=("cfg",))
def _build(snapshot, *, cfg):
    return _build_carry(snapshot, cfg)


@devprof.boundary("solver.candidates._build_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _build_sharded(snapshot, *, cfg, mesh):
    from koordinator_tpu.parallel.mesh import (
        POD_AXIS,
        snapshot_pod_partition_specs,
    )

    return jax.shard_map(
        lambda snap: _build_carry(snap, cfg),
        mesh=mesh,
        in_specs=(snapshot_pod_partition_specs(snapshot),),
        out_specs=(P(POD_AXIS, None), P(POD_AXIS)),
        check_vma=False,
    )(snapshot)


def _count_carry(snapshot, cfg):
    """Counts-only sweep: i64[NB, P] exact per-block feasible counts.
    No merges, no sorts — the cheap pass whose readback drives the
    pipelined build's block pruning."""
    nodes = snapshot.nodes
    n = nodes.allocatable.shape[0]
    b = _sweep_block(n, int(cfg.candidate_width))

    def step(carry, block):
        gidx = block * b + jnp.arange(b, dtype=jnp.int32)
        sub = dataclasses.replace(snapshot, nodes=_take_nodes(nodes, gidx))
        feas = feasibility_mask(sub, cfg)  # [P, B]
        return carry, jnp.sum(feas, axis=-1, dtype=jnp.int64)

    _, counts = lax.scan(
        step, 0, jnp.arange(n // b, dtype=jnp.int32)
    )
    return counts


@devprof.boundary("solver.candidates._count_blocks")
@partial(jax.jit, static_argnames=("cfg",))
def _count_blocks(snapshot, *, cfg):
    return _count_carry(snapshot, cfg)


@devprof.boundary("solver.candidates._count_blocks_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _count_blocks_sharded(snapshot, *, cfg, mesh):
    """Counts pass with the BLOCK axis sharded over a node-axis mesh:
    each device sweeps its local node shard's blocks (feasibility is
    index-free, so local node tables suffice; pod tables replicate
    under ``snapshot_partition_specs``), and the stacked [NB, P]
    output lands in global block order because device d's shard IS
    blocks [d*NB/D, (d+1)*NB/D)."""
    from koordinator_tpu.parallel.mesh import (
        snapshot_partition_specs,
    )
    from koordinator_tpu.parallel.mesh import CLUSTER_AXIS

    return jax.shard_map(
        lambda snap: _count_carry(snap, cfg),
        mesh=mesh,
        in_specs=(snapshot_partition_specs(snapshot),),
        out_specs=P(CLUSTER_AXIS, None),
        check_vma=False,
    )(snapshot)


@devprof.boundary("solver.candidates._extract_block")
@partial(jax.jit, static_argnames=("cfg",))
def _extract_block(snapshot, block, *, cfg):
    """One node block's candidate columns: i32[P, B] global node
    indices where feasible, sentinel N elsewhere.  ``block`` is
    TRACED (an i32 scalar), so ONE compiled program serves every
    block of a geometry and the pipelined build never retraces."""
    nodes = snapshot.nodes
    n = nodes.allocatable.shape[0]
    b = _sweep_block(n, int(cfg.candidate_width))
    gidx = block * b + jnp.arange(b, dtype=jnp.int32)
    sub = dataclasses.replace(snapshot, nodes=_take_nodes(nodes, gidx))
    feas = feasibility_mask(sub, cfg)  # [P, B]
    return jnp.where(feas, gidx[None, :], jnp.int32(n))


def _merge_lowest_host(cand: np.ndarray, new_idx: np.ndarray) -> np.ndarray:
    """Host-side twin of :func:`_merge_lowest`: exact integer sort, so
    the merged C-prefix is bit-identical to the device merge."""
    c = cand.shape[1]
    merged = np.sort(np.concatenate([cand, new_idx], axis=1), axis=1)
    return merged[:, :c]


def _build_pipelined(snapshot, cfg, node_mesh=None):
    """Pipelined cold build (ISSUE 20): byte-identical to
    :func:`_build`, ≥2x faster at large N.  Three legs:

    1. **counts pass** — one jitted sweep (block-axis sharded over
       ``node_mesh`` when one is configured) yields exact per-block
       feasible counts, no merge work;
    2. **block pruning** — block j can reach pod p's C-prefix only if
       it holds a feasible node for p AND fewer than C feasible nodes
       precede it (every preceding index is smaller, so a C-full
       prefix is final).  Any valid (non-overflowing) geometry has
       count <= C per pod, which makes every feasible-holding block
       needed for ITS pods but lets the sweep skip the (typically
       vast) feasibility deserts a 2^21-node axis is mostly made of;
    3. **pipelined extraction** — per-block feasibility launches (one
       traced-block program, no retraces) dispatched
       ``_PIPELINE_DEPTH`` ahead while the host sort-merges the
       previous block's readback: device compute for block i+1
       overlaps the merge of block i.

    Parity argument: the final lists are the C lowest feasible node
    indices per pod; pruned blocks provably cannot contribute to any
    C-prefix, int64 count sums are exact in any order, and the host
    integer sort is bit-identical to the device sort."""
    nodes, pods = snapshot.nodes, snapshot.pods
    n = nodes.allocatable.shape[0]
    p = pods.requests.shape[0]
    c = int(cfg.candidate_width)
    b = _sweep_block(n, c)
    if (
        node_mesh is not None and node_mesh.size > 1
        and n % node_mesh.size == 0 and (n // node_mesh.size) % b == 0
    ):
        counts = _count_blocks_sharded(snapshot, cfg=cfg, mesh=node_mesh)
    else:
        counts = _count_blocks(snapshot, cfg=cfg)
    counts_np = np.asarray(counts)  # [NB, P]
    count = counts_np.sum(axis=0, dtype=np.int64)  # exact totals
    before = np.cumsum(counts_np, axis=0, dtype=np.int64) - counts_np
    needed = np.nonzero(
        np.any((counts_np > 0) & (before < c), axis=1)
    )[0]
    cand = np.full((p, c), n, np.int32)
    inflight: deque = deque()
    for j in needed:
        inflight.append(_extract_block(snapshot, jnp.int32(j), cfg=cfg))
        if len(inflight) >= _PIPELINE_DEPTH:
            cand = _merge_lowest_host(
                cand, np.asarray(inflight.popleft())
            )
    while inflight:
        cand = _merge_lowest_host(cand, np.asarray(inflight.popleft()))
    return jnp.asarray(cand), jnp.asarray(count)


def _parallel_build_mode() -> str:
    return os.environ.get("KOORD_PARALLEL_BUILD", "auto")


def _refresh_carry(snapshot, cand, count, node_idx, pod_idx, cfg):
    """One exact merge-refresh:

    * dirty NODE columns — evict the dirty nodes from every list (a
      dirty node invalidates only the lists containing it), subtract
      them from the counts, re-evaluate just their feasibility
      ([P, dB] — dB is the padded dirty bucket, never N), and
      sort-merge the still-feasible ones back;
    * dirty POD rows — rebuilt from scratch by the blocked sweep over
      a gathered sub-snapshot, scattered with ``mode="drop"`` exactly
      like the incremental rescore's row pass.

    Precondition: the residency being advanced was non-overflowed
    (count <= C everywhere), so every previously-feasible dirty node
    IS in its lists and the count arithmetic stays exact — which is
    what keeps overflow detection truthful across any delta stream.
    Pad slots in ``node_idx``/``pod_idx`` carry the out-of-range
    sentinels ``_pad_rows`` wrote; they gather a clipped row whose
    result is masked or dropped."""
    nodes, pods = snapshot.nodes, snapshot.pods
    n = nodes.allocatable.shape[0]
    p = pods.requests.shape[0]
    member = jnp.any(
        cand[:, :, None] == node_idx[None, None, :], axis=-1
    ) & (cand < n)
    count = count - jnp.sum(member, axis=-1, dtype=jnp.int64)
    cand = jnp.where(member, jnp.int32(n), cand)
    sub = dataclasses.replace(
        snapshot, nodes=_take_nodes(nodes, jnp.clip(node_idx, 0, n - 1))
    )
    feas_d = feasibility_mask(sub, cfg) & (node_idx < n)[None, :]
    count = count + jnp.sum(feas_d, axis=-1, dtype=jnp.int64)
    new_idx = jnp.where(
        feas_d, node_idx[None, :].astype(jnp.int32), jnp.int32(n)
    )
    cand = _merge_lowest(cand, new_idx)
    # dirty pod rows: full per-row rebuild (the row's old list says
    # nothing about its new requests), scatter-dropped at pad slots
    sub_pods = _take_pods(pods, jnp.clip(pod_idx, 0, p - 1))
    row_cand, row_count = _build_carry(
        dataclasses.replace(snapshot, pods=sub_pods), cfg
    )
    cand = cand.at[pod_idx, :].set(row_cand, mode="drop")
    count = count.at[pod_idx].set(row_count, mode="drop")
    return cand, count


@devprof.boundary("solver.candidates._refresh")
@partial(jax.jit, static_argnames=("cfg",))
def _refresh(snapshot, cand, count, node_idx, pod_idx, *, cfg):
    return _refresh_carry(snapshot, cand, count, node_idx, pod_idx, cfg)


@devprof.boundary("solver.candidates._refresh_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _refresh_sharded(snapshot, cand, count, node_idx, pod_idx, *, cfg, mesh):
    from koordinator_tpu.parallel.mesh import (
        POD_AXIS,
        snapshot_pod_partition_specs,
    )

    cspec = P(POD_AXIS, None)

    def body(snap_l, cand_l, count_l, nidx, pidx):
        # node indices replicate (node tables are whole on every
        # device); pod indices rebase against the local shard like the
        # sharded rescore's dirty columns — foreign/pad rows rebase out
        # of range and drop
        p_local = snap_l.pods.requests.shape[0]
        start = lax.axis_index(POD_AXIS).astype(pidx.dtype) * p_local
        loc = pidx - start
        loc = jnp.where((loc >= 0) & (loc < p_local), loc, p_local)
        return _refresh_carry(snap_l, cand_l, count_l, nidx, loc, cfg)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            snapshot_pod_partition_specs(snapshot),
            cspec, P(POD_AXIS), P(), P(),
        ),
        out_specs=(cspec, P(POD_AXIS)),
        check_vma=False,
    )(snapshot, cand, count, node_idx, pod_idx)


def _score_carry(snapshot, cand, cfg):
    """Score the gathered [P, C] cells through the UNCHANGED cellwise
    ``score_all`` body (fit + loadaware + the full term stack): vmap
    over pods of a [1]-pod x [C]-node sub-snapshot — bit-identical to
    the dense cells by the cellwise term contract.  Sentinel slots
    gather a clipped real row; their feasibility is forced off after
    (never rely on the clip: node n-1's row would alias into pads)."""
    nodes, pods = snapshot.nodes, snapshot.pods
    n = nodes.allocatable.shape[0]
    p = pods.requests.shape[0]

    def row(pi, cidx):
        sub = dataclasses.replace(
            snapshot,
            nodes=_take_nodes(nodes, jnp.clip(cidx, 0, n - 1)),
            pods=_take_pods(pods, pi[None]),
        )
        s, f = score_all(sub, cfg)
        return s[0], f[0]

    scores, feas = jax.vmap(row)(jnp.arange(p), cand)
    return scores, feas & (cand < n)


@devprof.boundary("solver.candidates._score")
@partial(jax.jit, static_argnames=("cfg",))
def _score(snapshot, cand, *, cfg):
    return _score_carry(snapshot, cand, cfg)


@devprof.boundary("solver.candidates._score_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _score_sharded(snapshot, cand, *, cfg, mesh):
    from koordinator_tpu.parallel.mesh import (
        POD_AXIS,
        snapshot_pod_partition_specs,
    )

    cspec = P(POD_AXIS, None)
    return jax.shard_map(
        lambda snap, cd: _score_carry(snap, cd, cfg),
        mesh=mesh,
        in_specs=(snapshot_pod_partition_specs(snapshot), cspec),
        out_specs=(cspec, cspec),
        check_vma=False,
    )(snapshot, cand)


def _check_sparse_cfg(cfg) -> None:
    if int(cfg.candidate_width) <= 0:
        raise ValueError(
            "sparse candidate scoring needs cfg.candidate_width > 0 "
            f"(got {cfg.candidate_width!r})"
        )


def _check_pod_mesh(snapshot, mesh) -> None:
    p = snapshot.pods.requests.shape[0]
    if p % mesh.size:
        raise ValueError(
            f"pod bucket {p} does not divide over {mesh.size} devices; "
            "resize the pod mesh to a power-of-two prefix"
        )


def build_candidates(snapshot, cfg, mesh=None, node_mesh=None):
    """Cold build: (cand i32[P, C] ascending index lists with sentinel
    N in pad slots, count i64[P] exact feasible totals).  ``mesh``: a
    1-D pod mesh (parallel/mesh.py ``pod_mesh``) runs the sweep
    pod-parallel with zero collectives.  Without a pod mesh, large
    geometries route through the pipelined build (ISSUE 20, see
    :func:`_build_pipelined` — byte-identical, host merge overlapped
    with device compute, counts pass block-sharded over ``node_mesh``
    when one is configured); ``KOORD_PARALLEL_BUILD`` (0/1/auto)
    overrides the routing.  Callers must
    :func:`check_candidate_overflow` the count readback before serving
    from the lists."""
    _check_sparse_cfg(cfg)
    if mesh is not None and mesh.size > 1:
        _check_pod_mesh(snapshot, mesh)
        return _build_sharded(snapshot, cfg=cfg, mesh=mesh)
    n = snapshot.nodes.allocatable.shape[0]
    blocks = n // _sweep_block(n, int(cfg.candidate_width))
    mode = _parallel_build_mode()
    pipelined = (
        blocks >= _PARALLEL_MIN_BLOCKS if mode not in ("0", "1")
        else mode == "1"
    )
    if pipelined:
        return _build_pipelined(snapshot, cfg, node_mesh=node_mesh)
    return _build(snapshot, cfg=cfg)


def refresh_candidates(snapshot, cand, count, node_rows, pod_rows,
                       cfg, mesh=None):
    """Advance (cand, count) past a warm delta: ``node_rows`` /
    ``pod_rows`` are the unpadded unique dirty row sets the commits
    accumulated (bridge/state.py ``CandidateResidency``).  Exact under
    the non-overflow precondition (:func:`_refresh_carry`); dirty
    buckets ride the incremental rescore's power-of-two pads, so a
    steady warm stream holds zero jit cache misses."""
    _check_sparse_cfg(cfg)
    n = snapshot.nodes.allocatable.shape[0]
    p = snapshot.pods.requests.shape[0]
    node_idx = jnp.asarray(_pad_rows(node_rows, n))
    pod_idx = jnp.asarray(_pad_rows(pod_rows, p))
    if mesh is not None and mesh.size > 1:
        _check_pod_mesh(snapshot, mesh)
        return _refresh_sharded(
            snapshot, cand, count, node_idx, pod_idx, cfg=cfg, mesh=mesh
        )
    return _refresh(snapshot, cand, count, node_idx, pod_idx, cfg=cfg)


def score_candidates(snapshot, cand, cfg, mesh=None):
    """(scores i64[P, C], feasible bool[P, C]) of the gathered cells —
    the sparse engine's whole scoring cost, O(P x C) instead of
    O(P x N).  Feasible bits at real slots equal the dense engine's at
    (p, cand[p, c]); sentinel slots are infeasible."""
    _check_sparse_cfg(cfg)
    if mesh is not None and mesh.size > 1:
        _check_pod_mesh(snapshot, mesh)
        return _score_sharded(snapshot, cand, cfg=cfg, mesh=mesh)
    return _score(snapshot, cand, cfg=cfg)


@devprof.boundary("solver.candidates.sparse_top_k")
@partial(jax.jit, static_argnames=("k", "hi"))
def sparse_top_k(scores, feasible, cand, *, k, hi):
    """Serving top-k over the [P, C] cells, mapped back to real node
    ids: (top_scores i64[P, k], top_node i32[P, k], ok bool[P, k]).

    ``masked_top_k`` runs unchanged on the trailing candidate axis
    (same f32 fast path, same ``hi`` bound); winners map through the
    index lists via ``take_along_axis``.  Because lists are ASCENDING
    by node index, ``lax.top_k``'s lower-slot tie-break IS the dense
    engine's lower-node-index tie-break after the map.  ``ok`` is the
    per-winner feasibility the reply assembly gates on (the dense path
    derives it by gathering the [P, N] feasible tensor — which the
    sparse engine never owns); non-ok slots report node 0, which the
    gate keeps out of every reply byte."""
    from koordinator_tpu.solver.topk import masked_top_k

    ts, tc = masked_top_k(scores, feasible, k=k, hi=hi)
    ok = jnp.take_along_axis(feasible, tc, axis=-1)
    ti = jnp.take_along_axis(cand, tc, axis=-1).astype(jnp.int32)
    return ts, jnp.where(ok, ti, jnp.int32(0)), ok


def candidate_membership_mask(cand, num_nodes: int) -> jnp.ndarray:
    """bool[P, N] membership mask of the candidate lists — the
    assign-side bridge (parallel/shard_assign.py ``candidates=``): the
    wave engine ANDs it into its ``extra_mask`` seam, so gang/quota
    resolution (the replicated wave top-M merge) sees only candidate
    cells.  Sentinel slots are out of range and drop; this tensor is
    dense [P, N] by design — the wave assign already materializes
    per-wave [W, N] blocks, and the mask exists to CONSTRAIN that
    engine, not to replace it."""
    p = cand.shape[0]
    mask = jnp.zeros((p, int(num_nodes)), bool)
    return mask.at[jnp.arange(p)[:, None], cand].set(True, mode="drop")
