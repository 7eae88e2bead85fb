"""Incremental column/row rescore of the resident [P, N] score tensors.

The Score phase is one dense pods x nodes tensor program (ISSUE 9 —
the paper's whole premise), but a warm delta Sync touches a handful of
node rows: recomputing the entire [P, N] tensor for a 3-node delta
throws away 99.9% of the arithmetic.  The bridge keeps the last
launch's score/feasible tensors DEVICE-RESIDENT (bridge/state.py
``ScoreResidency``) and this module recomputes only what a batch of
committed deltas invalidated:

* **dirty columns** — node rows a delta scattered (or whose derived
  freshness flipped): gather those node rows, run the scoring math for
  every pod against just them (O(P x d)), and select the [P, d] result
  into the resident tensors through a node-to-column map (a column
  scatter needs more scoped VMEM than v5e grants).
* **dirty rows** — pod rows that changed (requests/estimated deltas,
  priority-class flips): gather those pod rows, score them against
  every node (O(d_p x N)), scatter the [d_p, N] result in.

Exactness contract: every term of the scoring math
(solver/greedy.py ``score_all`` — the SHARED body, so the engines
cannot drift) is cellwise in (pod row, node row), so gather-compute-
place produces the very same bits a full ``score_cycle`` would put
in those cells; untouched cells keep the bits the last launch wrote.
tests/test_score_incremental.py fuzzes randomized warm streams against
the full-rescore oracle byte-for-byte.

Compile economics: dirty counts vary per cycle, so the index vectors
are padded to the same power-of-two buckets the delta scatter uses
(pad slots carry an out-of-range index dropped by ``mode="drop"``) —
one compiled rescore per (geometry, dirty-bucket pair), zero jit cache
misses on a steady warm stream.  The dirty COUNT itself never crosses
the jit boundary (a traced ``n_dirty`` would retrace per value — the
koordlint retrace-hazard rule rejects that shape statically).

Donation: the resident ``scores`` tensor ([P, N] i64, the big one) is
donated — the pre-rescore buffer is dead the moment the new tensor
exists, so the update aliases in place.  ``feasible`` is NOT donated:
coalesced Score readbacks ``device_get`` the feasible tensor they
captured at launch, and a non-donating warm commit (derived-column
only) does not drain the pipeline — donating feasible could delete a
buffer an in-flight batch still reads.

Mesh (ISSUE 7 geometry): the score tensor shards ``P(None, "nodes")``
— column j lives with node j's snapshot rows — so the sharded rescore
is a ``shard_map`` where each device rebases the global dirty-column
indices against its own shard, recomputes with its LOCAL node rows,
and scatters only the columns it owns.  NO collective runs; in/out
specs are equal, so no resharding program is ever minted.

The sparse candidate engine (ISSUE 16, solver/candidates.py) reuses
this module's gather/pad helpers (``_take_nodes``/``_take_pods``/
``_pad_rows``) for its own dirty-row refreshes — same bucketing, same
OOB-sentinel drop semantics, same retrace-free contract.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from koordinator_tpu.model.snapshot import pad_bucket
from koordinator_tpu.obs import devprof
from koordinator_tpu.solver.greedy import score_all


def _take_nodes(nodes, idx):
    """NodeBatch with rows gathered at ``idx`` (in-range by contract:
    callers clip).  Optional leaves stay None; names stay static."""
    take = lambda a: None if a is None else jnp.take(a, idx, axis=0)
    return dataclasses.replace(
        nodes,
        allocatable=take(nodes.allocatable),
        requested=take(nodes.requested),
        usage=take(nodes.usage),
        metric_fresh=take(nodes.metric_fresh),
        valid=take(nodes.valid),
        agg_usage=take(nodes.agg_usage),
        agg_fresh=take(nodes.agg_fresh),
        prod_usage=take(nodes.prod_usage),
        accel_type=take(nodes.accel_type),
    )


def _take_pods(pods, idx):
    take = lambda a: jnp.take(a, idx, axis=0)
    opt = lambda a: None if a is None else take(a)
    return dataclasses.replace(
        pods,
        requests=take(pods.requests),
        estimated=take(pods.estimated),
        priority_class=take(pods.priority_class),
        qos=take(pods.qos),
        priority=take(pods.priority),
        gang_id=take(pods.gang_id),
        quota_id=take(pods.quota_id),
        valid=take(pods.valid),
        workload_class=opt(pods.workload_class),
        sensitivity=opt(pods.sensitivity),
    )


def _rescore_body(snapshot, scores, feasible, node_idx, pod_idx, cfg):
    """Column pass then row pass over one (shard-local) block.  The two
    passes overlap on (dirty pod, dirty node) cells with identical
    values — both compute the full-rescore bits — so the order is
    immaterial; pad/foreign slots carry out-of-range targets that
    ``mode="drop"`` discards."""
    nodes, pods = snapshot.nodes, snapshot.pods
    n_rows = nodes.allocatable.shape[0]
    p_rows = pods.requests.shape[0]
    # dirty COLUMNS: every pod vs the gathered node rows -> [P, dB]
    sub_nodes = _take_nodes(nodes, jnp.clip(node_idx, 0, n_rows - 1))
    s_cols, f_cols = score_all(
        dataclasses.replace(snapshot, nodes=sub_nodes), cfg
    )
    # placed by a select through a column map, not a column scatter:
    # XLA's TPU scatter of P-long column windows needs more scoped VMEM
    # than v5e grants (RESOURCE_EXHAUSTED at 16,384 x 2,048 with 64
    # dirty columns, tests/test_chip_compile.py); the select streams
    src = jnp.full((n_rows,), -1, jnp.int32).at[node_idx].set(
        jnp.arange(node_idx.shape[0], dtype=jnp.int32), mode="drop"
    )
    hit = (src >= 0)[None, :]
    col = jnp.maximum(src, 0)
    scores = jnp.where(hit, jnp.take(s_cols, col, axis=1), scores)
    feasible = jnp.where(hit, jnp.take(f_cols, col, axis=1), feasible)
    # dirty ROWS: the gathered pod rows vs every node -> [dB_p, N]
    sub_pods = _take_pods(pods, jnp.clip(pod_idx, 0, p_rows - 1))
    s_rows, f_rows = score_all(
        dataclasses.replace(snapshot, pods=sub_pods), cfg
    )
    scores = scores.at[pod_idx, :].set(s_rows, mode="drop")
    feasible = feasible.at[pod_idx, :].set(f_rows, mode="drop")
    return scores, feasible


@devprof.boundary("solver.incremental._rescore")
@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _rescore(snapshot, scores, feasible, node_idx, pod_idx, *, cfg):
    """Single-chip incremental rescore; ``scores`` is donated (the
    pre-rescore buffer is dead), ``feasible`` is copied (module
    docstring: in-flight readbacks hold it)."""
    return _rescore_body(snapshot, scores, feasible, node_idx, pod_idx, cfg)


@devprof.boundary("solver.incremental._rescore_sharded")
@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(1,))
def _rescore_sharded(snapshot, scores, feasible, node_idx, pod_idx, *, cfg, mesh):
    """Shard-LOCAL incremental rescore over the cluster mesh: the score
    tensor is ``P(None, "nodes")`` (column j with node j's rows), the
    dirty-column indices replicate, and each device rebases them
    against its own shard's column offset — foreign and pad columns
    rebase out of local range and drop, so a dirty column writes on
    exactly the device owning it.  The row pass scores the dirty pod
    rows against each device's LOCAL node shard and scatters its own
    [dB_p, N_local] block.  In/out specs equal: nothing regathers."""
    from jax.sharding import PartitionSpec as P

    from koordinator_tpu.parallel.mesh import (
        CLUSTER_AXIS,
        snapshot_partition_specs,
    )

    score_spec = P(None, CLUSTER_AXIS)

    def body(snap_local, scores_l, feasible_l, nidx, pidx):
        n_local = snap_local.nodes.allocatable.shape[0]
        start = jax.lax.axis_index(CLUSTER_AXIS).astype(nidx.dtype) * n_local
        loc = nidx - start
        owned = (loc >= 0) & (loc < n_local)
        loc = jnp.where(owned, loc, n_local)  # not-mine/pad -> dropped
        return _rescore_body(snap_local, scores_l, feasible_l, loc, pidx, cfg)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            snapshot_partition_specs(snapshot),
            score_spec, score_spec, P(), P(),
        ),
        out_specs=(score_spec, score_spec),
        check_vma=False,
    )(snapshot, scores, feasible, node_idx, pod_idx)


def _pad_rows(rows, oob: int) -> np.ndarray:
    """Sorted unique row indices padded to the power-of-two bucket with
    the out-of-range sentinel ``oob`` (``mode="drop"`` discards it) —
    the apply_flat_delta bucket discipline, so dirty-count variance
    never mints new compiled shapes."""
    rows = np.asarray(sorted(int(r) for r in rows), np.int64)
    bucket = pad_bucket(max(len(rows), 1))
    out = np.full(bucket, oob, np.int64)
    out[: len(rows)] = rows
    return out


def rescore_dirty(snapshot, scores, feasible, node_rows, pod_rows,
                  cfg, mesh=None):
    """Recompute the dirty columns/rows of the resident score tensors.

    ``scores``/``feasible`` are the resident [P, N] tensors of the LAST
    certified launch; ``node_rows``/``pod_rows`` are the (unpadded,
    unique) row indices every warm commit since then invalidated.
    Returns the advanced ``(scores, feasible)`` pair — bit-identical to
    ``score_cycle(snapshot, cfg)`` by the gather/scatter exactness
    contract (module docstring).

    ``scores`` is DONATED: callers must re-bind or drop their reference
    (the koordlint ``donation-safety`` rule checks call sites of this
    helper cross-module).  ``feasible`` is never donated — in-flight
    coalesced readbacks hold it.

    ``mesh``: the cluster mesh routes the shard-local program;
    ``scores``/``feasible`` must be ``P(None, "nodes")``-sharded over it
    (parallel/mesh.py ``score_sharding``) and the snapshot mesh-resident.
    """
    node_idx = jnp.asarray(_pad_rows(node_rows, scores.shape[1]))
    pod_idx = jnp.asarray(_pad_rows(pod_rows, scores.shape[0]))
    if mesh is not None and mesh.size > 1:
        kernel, kw = _rescore_sharded, {"mesh": mesh}
    else:
        kernel, kw = _rescore, {}
    return kernel(snapshot, scores, feasible, node_idx, pod_idx, cfg=cfg, **kw)
