"""Resident snapshot state for the scorer sidecar.

The host->device transfer is the boundary to engineer (SURVEY §5/§7), in
two layers:

* **host mirrors** — the server keeps numpy mirrors of every snapshot
  tensor; a warm Sync ships only sparse (index, value) deltas
  (native/koordnative.cpp codec) against them.  The mirrors are the
  source of truth: validation, i32-bounds checks and cold rebuilds all
  read them.
* **device residency** (the warm-cycle fast path) — the committed
  ``ClusterSnapshot``'s ``jax.Array`` tensors stay alive across Syncs.
  A delta frame is applied ON DEVICE as a jitted scatter
  (solver/resident.py, donating the dead pre-delta buffer); a full
  tensor of unchanged geometry re-uploads just that tensor; and derived
  columns (padded priority/gang/quota vectors, freshness masks) are
  rebuilt only when their wire columns actually changed.  Assign/Score
  then run straight off the resident arrays — a warm cycle pays
  O(changed), skipping the host re-encode and the full host->device
  re-upload entirely.

Any geometry change (table size, pad bucket, a tensor appearing or
disappearing) drops device residency and the next snapshot() is a cold
rebuild from the mirrors.  The two paths are bit-exact by construction
(the warm path edits the same padded cells the cold encode would write);
tests/test_resident_warm.py fuzzes random delta sequences against cold
re-encodes on both the scan and interpret-mode Pallas paths.

The resident snapshot carries NO name tuples: names are static pytree
metadata, so routing them through the jitted cycle would retrace it
whenever a pod name changes (every warm cycle on the Go seam).  Names
stay host-side on this object; replies are index-based.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from koordinator_tpu import native
from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import (
    ClusterSnapshot,
    GangTable,
    NodeBatch,
    PodBatch,
    PriorityClass,
    QuotaTable,
    pad_bucket,
    priority_class_column,
)

R = res.NUM_RESOURCES

logger = logging.getLogger(__name__)

# "no precomputed plan" sentinel for commit_donates/commit_sync —
# distinct from None, which is a real plan value meaning "go cold"
_PLAN_UNSET = object()


def decode_tensor(
    t: "pb2.Tensor", base: Optional[np.ndarray]
) -> Tuple[Optional[np.ndarray], str, Optional[np.ndarray], Optional[np.ndarray]]:
    """Decode a proto Tensor against the resident ``base`` mirror.

    Returns ``(mirror, kind, idx, val)`` where kind is "none" (message
    carries nothing; tensor unchanged), "full" (full payload) or "delta"
    (sparse update; idx/val are the validated flat indices and values so
    the device path can scatter them without re-diffing).
    """
    if t.data:
        arr = np.frombuffer(t.data, dtype="<i8").copy()
        return arr.reshape(tuple(t.shape)), "full", None, None
    if t.delta_idx:
        if base is None:
            raise ValueError("delta sync without a resident tensor")
        # the delta must target the RESIDENT shape: a client with a stale
        # differently-shaped mirror emits indices that may all land
        # inside the resident cell count yet write the wrong cells —
        # shape equality rejects every mismatch, not just the
        # out-of-range subset
        if t.shape and tuple(t.shape) != base.shape:
            raise ValueError(
                f"delta shape {tuple(t.shape)} != resident {base.shape}"
            )
        idx = np.frombuffer(t.delta_idx, dtype="<i8")
        val = np.frombuffer(t.delta_val, dtype="<i8")
        if len(idx) != len(val):
            raise ValueError(
                f"delta index/value length mismatch: {len(idx)} vs {len(val)}"
            )
        # duplicate indices are rejected, not tolerated: the host path
        # (native.delta_apply) is sequential last-wins but the device
        # scatter's duplicate semantics are implementation-defined, so a
        # frame with repeats could silently split the mirror from the
        # resident tensors — and no honest delta encoder emits them
        if len(idx) != len(np.unique(idx)):
            raise ValueError("delta carries duplicate indices")
        # bounds-check BEFORE the native path: delta_apply writes through
        # raw pointers, so an out-of-range index from a hostile frame
        # would corrupt server memory instead of raising
        if len(idx) and (int(idx.min()) < 0 or int(idx.max()) >= base.size):
            raise ValueError(
                f"delta index out of range for resident tensor of "
                f"{base.size} cells"
            )
        out = base.copy()
        native.delta_apply(out, idx, val)
        return out, "delta", idx, val
    return None, "none", None, None


def tensor_to_numpy(
    t: "pb2.Tensor", base: Optional[np.ndarray]
) -> Optional[np.ndarray]:
    """Decode a proto Tensor: full payload, or sparse delta onto ``base``.

    Returns the new mirror array, or None when the message carries nothing
    (tensor unchanged since the last sync).
    """
    arr, kind, _, _ = decode_tensor(t, base)
    return arr if kind != "none" else None


def numpy_to_tensor(
    arr: np.ndarray, prev: Optional[np.ndarray] = None, max_delta_ratio: float = 0.25
) -> "pb2.Tensor":
    """Encode full, or as a sparse delta when <= max_delta_ratio changed."""
    t = pb2.Tensor()
    arr = np.ascontiguousarray(arr, dtype=np.int64)
    t.shape.extend(arr.shape)
    if prev is not None and prev.shape == arr.shape:
        enc = native.delta_encode(
            prev, arr, max_changes=max(1, int(arr.size * max_delta_ratio))
        )
        if enc is not None:
            idx, val = enc
            t.delta_idx = idx.astype("<i8").tobytes()
            t.delta_val = val.astype("<i8").tobytes()
            return t
    t.data = arr.astype("<i8").tobytes()
    return t


def _pad_rows_to(a, rows):
    """Pad (or keep) leading axis to ``rows`` regardless of current length
    (mirrors the length-agnostic padded()/_pad2 helpers — a stale mirror
    after a node-count change must never produce a wrong-shaped tensor)."""
    a = np.asarray(a)
    if a.shape[0] >= rows:
        return a[:rows]
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad)


def _pc_column(explicit, priority, P, pb):
    col = np.full(pb, int(PriorityClass.NONE), np.int32)
    if explicit is not None:
        col[: len(explicit)] = explicit[:pb]
    else:
        col[:P] = priority_class_column(priority[:P])
    return col


def _present(a: Optional[np.ndarray]) -> bool:
    return a is not None and a.size > 0


# wire tensors that ride the sparse-delta path, keyed by mirror attribute
_DELTA_TENSORS = (
    "node_alloc",
    "node_requested",
    "node_usage",
    "node_agg",
    "node_agg_fresh",
    "node_prod",
    "pod_requests",
    "pod_estimated",
    "quota_runtime",
    "quota_used",
    "quota_limited",
    # fused-term tensors (ISSUE 15): the Synergy sensitivity profile and
    # the Gavel throughput matrix delta-sync like every snapshot tensor
    "pod_sensitivity",
    "term_throughput",
)

# score-relevant tensors (ISSUE 9): which resident mirrors feed the
# stateless score_cycle math — a delta to one of these dirties the
# touched ROWS of the resident [P, N] score tensors.  Quota tensors are
# deliberately absent: score_cycle reads no quota state (quota admission
# lives in the sequential Assign step only), so a quota-only Sync leaves
# the resident score tensors exactly valid — zero columns to rescore.
_SCORE_NODE_TENSORS = ("node_alloc", "node_requested", "node_usage",
                       "node_agg", "node_agg_fresh", "node_prod")
_SCORE_POD_TENSORS = ("pod_requests", "pod_estimated", "pod_sensitivity")
# the throughput matrix is neither node- nor pod-major: a delta to cell
# (c, a) invalidates the score COLUMNS of every node whose (clipped)
# accelerator type is ``a`` — _score_dirty_rows attributes it through
# the accel mirror, so a one-type matrix update rescores only that
# type's node columns (O(dirty), the ISSUE-15 acceptance)


class ScoreResidency:
    """The [P, N] score/feasible tensors as first-class device-resident
    leaves (ISSUE 9), plus the dirty row/column sets accumulated since
    the launch that certified them.

    ``scores``/``feasible`` are the padded tensors the last Score launch
    produced (node-axis-sharded ``P(None, "nodes")`` when mesh-resident);
    every warm commit unions the rows it invalidated into
    ``dirty_nodes``/``dirty_pods`` instead of discarding the tensors —
    the generation advances, the derived result advances with it.  The
    next Score recomputes only the dirty columns/rows
    (solver/incremental.py ``rescore_dirty``) and the sets clear.

    ``cfg`` is the CycleConfig the tensors were scored under: a config
    change means a different scoring program, so the servicer drops the
    residency rather than advance tensors it cannot certify.
    """

    __slots__ = ("cfg", "scores", "feasible", "dirty_nodes", "dirty_pods")

    def __init__(self, cfg, scores, feasible):
        self.cfg = cfg
        self.scores = scores
        self.feasible = feasible
        self.dirty_nodes: set = set()
        self.dirty_pods: set = set()


class CandidateResidency:
    """The sparse engine's device-resident [P, C] candidate-index map
    (ISSUE 16, solver/candidates.py) plus the exact per-pod feasible
    counts and the dirt accumulated since the launch that built them.

    The same commit seam that advances :class:`ScoreResidency` advances
    this: a warm commit unions its invalidated rows into
    ``dirty_nodes``/``dirty_pods`` (a dirty node invalidates only the
    candidate lists containing it — the next Score's lazy
    merge-refresh evicts and re-merges just those entries), and an
    attribution-losing commit (full re-upload) or a geometry move
    drops the residency for a cold rebuild.  The dirty sets are a
    conservative superset of what feasibility actually read: a
    score-only delta (e.g. sensitivity) forces a harmless re-merge,
    never a wrong list.

    ``merges`` counts exact merge-refreshes since the last full build —
    the staleness bound (``cfg.candidate_max_stale``) forces a full
    rebuild (refresh reason "stale") once the chain grows past it.
    ``count`` is the EXACT per-pod feasible total, maintained through
    every merge; the serving path refuses (``CandidateOverflow``)
    whenever it exceeds C rather than serve a truncated list.
    """

    __slots__ = ("cfg", "idx", "count", "dirty_nodes", "dirty_pods",
                 "merges")

    def __init__(self, cfg, idx, count, merges: int = 0):
        self.cfg = cfg
        self.idx = idx
        self.count = count
        self.dirty_nodes: set = set()
        self.dirty_pods: set = set()
        self.merges = int(merges)


# companions reset to defaults when a full tensor changes the node table
# size (ADVICE r5: a stale differently-shaped column must not linger to
# fail later at snapshot build).  node_requested/node_usage are included:
# a resize frame may legally omit them, and an old-shaped mirror would
# otherwise be padded/truncated onto the NEW nodes' rows at snapshot
# build — silently wrong data, or a broadcast error under a smaller
# explicit bucket
_NODE_COMPANIONS = ("node_fresh", "node_names", "node_agg", "node_agg_fresh",
                    "node_prod", "node_requested", "node_usage", "node_accel")
# NOTE: gang_min is deliberately NOT a pod companion — the gang table's
# shape is per-gang, not per-pod (like the quota tables), so resetting it
# on a pod resize would wipe gang gating while the new pod table's
# gang_id column still references the gangs
_POD_COMPANIONS = ("pod_priority", "pod_priority_class", "pod_gang",
                   "pod_quota", "pod_names", "pod_estimated",
                   "pod_workload", "pod_sensitivity")
_COMPANION_DEFAULTS = {"node_names": (), "pod_names": ()}


class ResidentState:
    """Numpy mirrors + the device-resident ClusterSnapshot built from them.

    ``mesh``: a cluster mesh (parallel/mesh.py ``cluster_mesh``) makes
    the resident snapshot MESH-SHARDED (ISSUE 7): node tensors split
    along the mesh's node axis (each device holds one shard of the
    cluster — the combined HBM is the capacity), pod rows and the
    gang/quota tables replicate, and every leaf carries the
    ``NamedSharding`` that ``parallel.mesh.snapshot_shardings``
    prescribes — the per-field builders here apply the same policy
    through ``node_sharding``/``replicated_sharding``, and
    tests/test_mesh_resident.py asserts the two stay in lockstep
    leaf-for-leaf (a field classified differently in the two places is
    a test failure, not silent mis-sharding).  Warm
    delta Syncs scatter SHARD-LOCALLY (solver/resident.py
    ``_scatter_flat_sharded``): a delta for node *j* lands on the one
    device owning *j*'s rows, no all-gather, no full re-upload — the
    same O(changed) warm path, now over N chips.  A node bucket that
    does not divide over the mesh falls back to single-chip placement
    for that geometry (logged once); buckets are powers of two, so any
    power-of-two device prefix always divides.
    """

    def __init__(self, mesh=None):
        self.mesh = mesh
        self._mesh_skip_warned: set = set()
        self.node_alloc: Optional[np.ndarray] = None
        self.node_requested: Optional[np.ndarray] = None
        self.node_usage: Optional[np.ndarray] = None
        self.node_fresh: Optional[np.ndarray] = None
        self.node_agg: Optional[np.ndarray] = None  # [N, A, R]
        self.node_agg_fresh: Optional[np.ndarray] = None
        self.node_prod: Optional[np.ndarray] = None
        self.node_names: tuple = ()
        self.pod_requests: Optional[np.ndarray] = None
        self.pod_estimated: Optional[np.ndarray] = None
        self.pod_priority: Optional[np.ndarray] = None
        self.pod_priority_class: Optional[np.ndarray] = None
        self.pod_gang: Optional[np.ndarray] = None
        self.pod_quota: Optional[np.ndarray] = None
        self.pod_names: tuple = ()
        self.gang_min: Optional[np.ndarray] = None
        self.quota_runtime: Optional[np.ndarray] = None
        self.quota_used: Optional[np.ndarray] = None
        self.quota_limited: Optional[np.ndarray] = None
        # fused-term mirrors (ISSUE 15): accel/workload columns plus the
        # sensitivity and throughput tensors; None = never synced (the
        # terms are inert for the missing data)
        self.node_accel: Optional[np.ndarray] = None
        self.pod_workload: Optional[np.ndarray] = None
        self.pod_sensitivity: Optional[np.ndarray] = None
        self.term_throughput: Optional[np.ndarray] = None
        self.node_bucket = 0
        self.pod_bucket = 0
        self._snapshot: Optional[ClusterSnapshot] = None
        # resident [P, N] score/feasible tensors + accumulated dirt
        # (ISSUE 9); populated by the servicer's Score launches via
        # store_score_result, advanced by warm commits, dropped cold
        self._score_res: Optional[ScoreResidency] = None
        # resident [P, C] sparse candidate lists + exact feasible
        # counts (ISSUE 16); populated by sparse Score launches via
        # store_candidates, advanced by warm commits, dropped cold
        self._cand_res: Optional[CandidateResidency] = None
        self._i32_ok: Optional[bool] = None
        # observability: how the last Sync landed on the device
        # ("cold" = residency dropped, rebuild at next snapshot();
        #  "warm" = resident tensors updated in place)
        self.last_sync_path = "cold"

    def apply_sync(self, reqmsg: "pb2.SyncRequest", spans=None) -> dict:
        """Decode EVERYTHING first, commit only if every tensor decoded:
        a rejected frame (bad delta shape/index, missing first-sync
        tensors) must leave the resident state untouched — a torn
        half-applied sync would hand every OTHER client a corrupted
        delta baseline behind an unbumped generation.

        ``spans``: an optional ``obs.spans.SpanRecorder``; the host-side
        decode ("sync_decode") and the on-device warm update
        ("delta_scatter") are recorded as stages of the upcoming cycle.
        Returns a summary dict for the scorer metric families:
        ``{"path": "warm"|"cold", "delta_tensors": n, "full_tensors": n}``.

        One-shot convenience over the two-phase ``stage_sync`` /
        ``commit_sync`` seam the coalescing pipeline uses (ISSUE 5): the
        server runs the protobuf->numpy decode OUTSIDE its device
        critical section — decode of Sync k+1 overlaps the on-device
        delta scatter of cycle k — and commits under its state lock.
        """
        return self.commit_sync(
            self.stage_sync(reqmsg, spans=spans), spans=spans
        )

    def stage_sync(self, reqmsg: "pb2.SyncRequest", spans=None):
        """Phase 1 — pure decode/validate.  Mutates NOTHING; every
        validation error (bad delta shape/index, duplicate indices,
        missing first-sync tensors, pre-resize companions) raises here,
        so a frame that passes staging always commits.  The caller must
        hold whatever serializes Syncs (the servicer's ``_sync_lock``):
        deltas are validated against the current mirrors, so another
        Sync committing mid-decode would invalidate the staging."""
        from koordinator_tpu.obs.spans import maybe_span

        with maybe_span(spans, "sync_decode"):
            return self._decode_sync(reqmsg)

    def plan_commit(self, staged_tinfo):
        """Compute the device-update plan for a staged frame against
        the current (pre-commit) mirrors and residency.  Pure planning:
        mutates nothing.  The plan depends on whether a snapshot is
        resident, and residency can flip at any *launch* (a Score's
        launch section lazily cold-rebuilds via ``snapshot()``) — so a
        plan that will gate or feed a commit must be computed with the
        dispatch launch lock held (``run_exclusive`` evaluates its
        ``drain`` callable exactly there) and handed to ``commit_sync``
        via its ``plan=`` parameter rather than recomputed."""
        staged, tinfo = staged_tinfo
        return self._warm_plan(staged, tinfo)

    def commit_donates(self, staged_tinfo, plan=_PLAN_UNSET) -> bool:
        """Whether committing this staged frame will DONATE resident
        device buffers (a warm plan with at least one delta scatter —
        solver/resident.py apply_flat_delta donates the dead pre-delta
        buffer).  The pipelined dispatcher (ISSUE 6) uses this to pick
        the commit barrier: a donating commit must drain in-flight
        launches (``run_exclusive(fn, drain=True)``) because deleting a
        donated buffer would invalidate python references a launched-
        but-unread batch still holds, while a cold or full-upload
        commit only needs launch ordering — in-flight batches keep
        their own snapshot references alive, so the pipeline keeps
        flowing.  Pass ``plan=`` from :meth:`plan_commit` to decide on
        the plan the commit will actually run (and to avoid planning
        twice); call between ``stage_sync`` and ``commit_sync`` under
        the same Sync serialization."""
        if plan is _PLAN_UNSET:
            plan = self.plan_commit(staged_tinfo)
        if plan is None:
            return False
        tensor_updates, _ = plan
        return any(u[0] == "delta" for u in tensor_updates.values())

    def commit_sync(self, staged_tinfo, spans=None, plan=_PLAN_UNSET) -> dict:
        """Phase 2 — atomic commit of a staged frame + the device-side
        warm update.  The delta scatter donates the pre-delta resident
        buffers, so the caller must hold the device-dispatch lock
        (bridge/coalesce.py run_exclusive, drained when
        ``commit_donates`` says so) to keep the donation from
        invalidating arrays a coalesced Score batch captured but has
        not read back yet.  ``plan=`` accepts the
        :meth:`plan_commit` result the drain decision was made on, so
        the barrier and the commit provably act on the same plan (and
        the full-tensor ``np.array_equal`` sweep runs once per Sync,
        not twice)."""
        from koordinator_tpu.obs.spans import maybe_span

        staged, tinfo = staged_tinfo
        if plan is _PLAN_UNSET:
            # device-update plan against the PRE-commit mirrors
            plan = self._warm_plan(staged, tinfo)
        # dirty score rows/columns this commit invalidates (ISSUE 9) —
        # computed against the PRE-commit mirrors, like the plan
        score_dirty = (
            self._score_dirty_rows(staged, plan) if plan is not None else None
        )
        # atomic commit point: nothing above mutated self
        for key, value in staged.items():
            setattr(self, key, value)
        if plan is None:
            self._snapshot = None  # cold: rebuilt lazily at snapshot()
            self._score_res = None  # geometry moved: nothing to advance
            self._cand_res = None
            self.last_sync_path = "cold"
        else:
            try:
                with maybe_span(spans, "delta_scatter"):
                    self._snapshot = self._apply_warm(plan)
                self.last_sync_path = "warm"
                self._note_score_dirty(score_dirty)
                self._note_candidate_dirty(score_dirty)
            except Exception:
                # a torn device update may have donated buffers out of the
                # old snapshot: drop residency, the mirrors stay truthful
                # and the next snapshot() cold-rebuilds from them
                logger.exception(
                    "warm device update failed; falling back to cold rebuild"
                )
                self._snapshot = None
                self._score_res = None
                self._cand_res = None
                self.last_sync_path = "cold"
        self._i32_ok = None
        kinds = [kind for kind, _, _ in tinfo.values()]
        return {
            "path": self.last_sync_path,
            "delta_tensors": kinds.count("delta"),
            "full_tensors": kinds.count("full"),
        }

    def export_sync_request(self) -> Optional["pb2.SyncRequest"]:
        """Full-state ``SyncRequest`` rebuilt from the host mirrors —
        the replication tier's one-shot full-resync payload (ISSUE 8):
        a follower that applies this onto a FRESH ResidentState ends
        with mirrors byte-identical to this one's (the same wire
        decode both sides; tests/test_replication.py asserts the
        round trip leaf-for-leaf).  Explicit buckets ride along so the
        follower pads — and compiles — the very same geometry.  Returns
        None before the first Sync (nothing to replicate yet; the
        follower resets to the empty pre-first-Sync state instead)."""
        if self.node_alloc is None or self.pod_requests is None:
            return None
        req = pb2.SyncRequest(
            node_bucket=self.node_bucket, pod_bucket=self.pod_bucket
        )
        for target, arr in (
            (req.nodes.allocatable, self.node_alloc),
            (req.nodes.requested, self.node_requested),
            (req.nodes.usage, self.node_usage),
            (req.nodes.agg_usage, self.node_agg),
            (req.nodes.agg_fresh, self.node_agg_fresh),
            (req.nodes.prod_usage, self.node_prod),
            (req.pods.requests, self.pod_requests),
            (req.pods.estimated, self.pod_estimated),
            (req.pods.sensitivity, self.pod_sensitivity),
            (req.quotas.runtime, self.quota_runtime),
            (req.quotas.used, self.quota_used),
            (req.quotas.limited, self.quota_limited),
            (req.terms.throughput, self.term_throughput),
        ):
            if _present(arr):
                # prev=None: always the full payload, never a delta —
                # the receiver has no baseline by definition
                target.CopyFrom(numpy_to_tensor(np.asarray(arr, np.int64)))
        if self.node_names:
            req.nodes.names.extend(self.node_names)
        if self.node_fresh is not None and len(self.node_fresh):
            req.nodes.metric_fresh.extend(bool(b) for b in self.node_fresh)
        if self.pod_names:
            req.pods.names.extend(self.pod_names)
        for target, arr in (
            (req.pods.priority, self.pod_priority),
            (req.pods.priority_class, self.pod_priority_class),
            (req.pods.gang_id, self.pod_gang),
            (req.pods.quota_id, self.pod_quota),
            (req.gangs.min_member, self.gang_min),
            (req.nodes.accel_type, self.node_accel),
            (req.pods.workload_class, self.pod_workload),
        ):
            if arr is not None and len(arr):
                target.extend(int(v) for v in arr)
        return req

    def _decode_sync(self, reqmsg: "pb2.SyncRequest"):
        """The pure decode/validate half of apply_sync: returns the
        staged mirror values and per-tensor wire info without mutating
        any resident state."""
        n = reqmsg.nodes
        p = reqmsg.pods
        wire = {
            "node_alloc": n.allocatable,
            "node_requested": n.requested,
            "node_usage": n.usage,
            "node_agg": n.agg_usage,
            "node_agg_fresh": n.agg_fresh,
            "node_prod": n.prod_usage,
            "pod_requests": p.requests,
            "pod_estimated": p.estimated,
            "quota_runtime": reqmsg.quotas.runtime,
            "quota_used": reqmsg.quotas.used,
            "quota_limited": reqmsg.quotas.limited,
            "pod_sensitivity": p.sensitivity,
            "term_throughput": reqmsg.terms.throughput,
        }
        staged: Dict[str, object] = {}
        tinfo: Dict[str, tuple] = {}
        for key, tensor in wire.items():
            current = getattr(self, key)
            arr, kind, idx, val = decode_tensor(tensor, current)
            staged[key] = current if kind == "none" else arr
            tinfo[key] = (kind, idx, val)
        if staged["node_alloc"] is None or staged["pod_requests"] is None:
            raise ValueError("first Sync must carry full node and pod tensors")
        if n.metric_fresh:
            staged["node_fresh"] = np.asarray(list(n.metric_fresh), dtype=bool)
        if n.names:
            staged["node_names"] = tuple(n.names)
        if p.priority:
            staged["pod_priority"] = np.asarray(list(p.priority), dtype=np.int64)
        if p.priority_class:
            staged["pod_priority_class"] = np.asarray(
                list(p.priority_class), dtype=np.int32
            )
        if p.gang_id:
            staged["pod_gang"] = np.asarray(list(p.gang_id), dtype=np.int32)
        if p.quota_id:
            staged["pod_quota"] = np.asarray(list(p.quota_id), dtype=np.int32)
        if p.names:
            staged["pod_names"] = tuple(p.names)
        if reqmsg.gangs.min_member:
            staged["gang_min"] = np.asarray(
                list(reqmsg.gangs.min_member), np.int32
            )
        if n.accel_type:
            staged["node_accel"] = np.asarray(list(n.accel_type), np.int32)
        if p.workload_class:
            staged["pod_workload"] = np.asarray(
                list(p.workload_class), np.int32
            )
        # explicit wire buckets win; otherwise a warm frame that omits
        # them INHERITS the resident bucket (sticky-grow) instead of
        # recomputing pad_bucket and silently reshaping — and recompiling
        # — the resident snapshot mid-stream
        def bucket(wire_value, current, rows):
            if wire_value:
                return int(wire_value)
            if current and current >= rows:
                return current
            return pad_bucket(rows)

        staged["node_bucket"] = bucket(
            reqmsg.node_bucket, self.node_bucket,
            staged["node_alloc"].shape[0],
        )
        staged["pod_bucket"] = bucket(
            reqmsg.pod_bucket, self.pod_bucket,
            staged["pod_requests"].shape[0],
        )
        self._reset_companions(staged, tinfo)
        return staged, tinfo

    # -- companion resets (ADVICE r5) --
    def _reset_companions(self, staged: Dict[str, object], tinfo) -> None:
        """When a full tensor changes a table's row count, omitted
        companion columns reset to defaults of the new shape instead of
        lingering at the stale shape to fail later at snapshot build.
        (None means "use the default of the current shape" everywhere in
        this class: all-fresh, zero priority, no gang/quota membership,
        estimated = requests.)"""
        def rows(a):
            return -1 if a is None else a.shape[0]

        def reset(companions, new_rows):
            for key in companions:
                if key in _DELTA_TENSORS:
                    # carried-over tensor mirror (nothing in this frame):
                    # its rows no longer match the new table
                    if tinfo[key][0] == "none":
                        staged[key] = None
                    elif rows(staged[key]) != new_rows:
                        # a delta (validated against the PRE-resize
                        # shape) or an old-shaped full tensor riding the
                        # same frame as the resize: committing it would
                        # silently pad stale rows onto the new table
                        raise ValueError(
                            f"{key} targets the pre-resize table "
                            f"({rows(staged[key])} rows != {new_rows})"
                        )
                elif key not in staged:
                    staged[key] = _COMPANION_DEFAULTS.get(key)

        if rows(staged["node_alloc"]) != rows(self.node_alloc):
            reset(_NODE_COMPANIONS, rows(staged["node_alloc"]))
        if rows(staged["pod_requests"]) != rows(self.pod_requests):
            reset(_POD_COMPANIONS, rows(staged["pod_requests"]))

    # -- warm-path planning / application --
    def _warm_plan(self, staged, tinfo):
        """Decide how this Sync lands on the resident device snapshot.

        Returns None when residency must drop (no resident snapshot, or
        any geometry change: table rows, pad buckets, a tensor or table
        appearing/disappearing).  Otherwise returns
        ``(tensor_updates, derived)`` where tensor_updates maps mirror
        keys to ("delta", idx, val) / ("full",) and derived is the set of
        scalar-derived device columns to rebuild.  Runs BEFORE the mirror
        commit so it can compare staged against current values."""
        if self._snapshot is None:
            return None
        if (
            staged["node_bucket"] != self.node_bucket
            or staged["pod_bucket"] != self.pod_bucket
        ):
            return None

        def shape(a):
            return None if a is None else a.shape

        # geometry must be identical for every resident tensor, and
        # presence flips (None <-> array, empty <-> non-empty) change the
        # snapshot structure -> cold
        for key in _DELTA_TENSORS:
            old, new = getattr(self, key), staged[key]
            if _present(old) != _present(new):
                return None
            if _present(old) and shape(old) != shape(new):
                return None
        old_gang = self.gang_min if self.gang_min is not None else ()
        new_gang = staged.get("gang_min", self.gang_min)
        new_gang = new_gang if new_gang is not None else ()
        if len(old_gang) != len(new_gang):
            return None
        # a freshness column of the wrong length would fail the cold
        # build too; surface it there instead of a device-shape error
        new_fresh = staged.get("node_fresh", self.node_fresh)
        if new_fresh is not None and len(new_fresh) != staged["node_alloc"].shape[0]:
            return None

        tensor_updates = {}
        for key in _DELTA_TENSORS:
            kind, idx, val = tinfo[key]
            if kind == "delta":
                tensor_updates[key] = ("delta", idx, val)
            elif kind == "full":
                if not np.array_equal(staged[key], getattr(self, key)):
                    tensor_updates[key] = ("full",)
        # estimated falls back to requests while never synced: a requests
        # update must land on the estimated device tensor too
        if staged["pod_estimated"] is None and "pod_requests" in tensor_updates:
            tensor_updates["pod_estimated_from_requests"] = tensor_updates[
                "pod_requests"
            ]

        # first appearance of a term COLUMN (ISSUE 15): the resident
        # snapshot gains a leaf (None -> array), which changes the
        # pytree structure every downstream jit keys on — one cold
        # rebuild, exactly like a tensor appearing in _DELTA_TENSORS
        for key in ("node_accel", "pod_workload"):
            if staged.get(key) is not None and getattr(self, key) is None:
                return None

        derived = set()
        for key in ("node_fresh", "pod_priority", "pod_priority_class",
                    "pod_gang", "pod_quota", "gang_min",
                    "node_accel", "pod_workload"):
            if key not in staged:
                continue
            old = getattr(self, key)
            if old is None or not np.array_equal(
                np.asarray(staged[key]), np.asarray(old)
            ):
                derived.add(key)
        if "pod_priority" in derived and staged.get(
            "pod_priority_class", self.pod_priority_class
        ) is None:
            # priority_class is derived from priority bands when the wire
            # never sent explicit classes
            derived.add("pod_priority_class")
        return tensor_updates, derived

    def _apply_warm(self, plan) -> ClusterSnapshot:
        """Apply a warm plan to the resident snapshot (mirrors are already
        committed).  Delta tensors scatter on device (donating the dead
        buffer); full tensors re-upload just themselves; derived columns
        rebuild through the same builders the cold path uses."""
        from koordinator_tpu.solver.resident import apply_flat_delta

        tensor_updates, derived = plan
        snap = self._snapshot
        nodes, pods, quotas = snap.nodes, snap.pods, snap.quotas
        mesh = self.active_mesh()

        def updated(dev_arr, key, update):
            if update[0] == "delta":
                # node tensors scatter SHARD-LOCALLY on the mesh: only
                # the device owning the touched rows writes (pod/quota
                # tensors replicate, so their scatter runs everywhere —
                # identical values, still donated in place)
                return apply_flat_delta(
                    dev_arr, update[1], update[2],
                    mesh=mesh if key.startswith("node_") else None,
                )
            return None  # full: rebuilt below from the committed mirror

        node_patch = {}
        for key, field in (
            ("node_alloc", "allocatable"),
            ("node_requested", "requested"),
            ("node_usage", "usage"),
        ):
            if key in tensor_updates:
                new = updated(getattr(nodes, field), key, tensor_updates[key])
                node_patch[field] = (
                    new if new is not None
                    else self._dev_padded2(key, self.node_bucket)
                )
        for key, field, builder in (
            ("node_agg", "agg_usage", self._dev_agg_usage),
            ("node_agg_fresh", "agg_fresh", self._dev_agg_fresh),
            ("node_prod", "prod_usage", self._dev_prod_usage),
        ):
            if key in tensor_updates:
                new = updated(getattr(nodes, field), key, tensor_updates[key])
                node_patch[field] = new if new is not None else builder()
        if "node_fresh" in derived:
            node_patch["metric_fresh"] = self._dev_metric_fresh()
        if "node_accel" in derived:
            node_patch["accel_type"] = self._dev_accel_type()

        pod_patch = {}
        if "pod_requests" in tensor_updates:
            new = updated(pods.requests, "pod_requests",
                          tensor_updates["pod_requests"])
            pod_patch["requests"] = (
                new if new is not None
                else self._dev_padded2("pod_requests", self.pod_bucket)
            )
        est_update = tensor_updates.get(
            "pod_estimated", tensor_updates.get("pod_estimated_from_requests")
        )
        if est_update is not None:
            new = updated(pods.estimated, "pod_estimated", est_update)
            pod_patch["estimated"] = (
                new if new is not None else self._dev_estimated()
            )
        if "pod_sensitivity" in tensor_updates:
            new = updated(pods.sensitivity, "pod_sensitivity",
                          tensor_updates["pod_sensitivity"])
            pod_patch["sensitivity"] = (
                new if new is not None else self._dev_sensitivity()
            )
        if "pod_priority" in derived:
            pod_patch["priority"] = self._dev_priority()
        if "pod_priority_class" in derived:
            pod_patch["priority_class"] = self._dev_priority_class()
        if "pod_gang" in derived:
            pod_patch["gang_id"] = self._dev_gang_id()
        if "pod_quota" in derived:
            pod_patch["quota_id"] = self._dev_quota_id()
        if "pod_workload" in derived:
            pod_patch["workload_class"] = self._dev_workload_class()

        quota_patch = {}
        for key, field in (
            ("quota_runtime", "runtime"),
            ("quota_used", "used"),
            ("quota_limited", "limited"),
        ):
            if key in tensor_updates:
                new = updated(getattr(quotas, field), key, tensor_updates[key])
                if new is None:
                    arr = getattr(self, key)
                    new = self._place_rep(
                        arr.astype(bool) if field == "limited" else arr
                    )
                quota_patch[field] = new

        throughput = snap.throughput
        if "term_throughput" in tensor_updates:
            # replicated side table: the scatter runs on every device
            # with identical values, like the pod/quota tensors
            new = updated(snap.throughput, "term_throughput",
                          tensor_updates["term_throughput"])
            throughput = new if new is not None else self._dev_throughput()

        if node_patch:
            nodes = dataclasses.replace(nodes, **node_patch)
        if pod_patch:
            pods = dataclasses.replace(pods, **pod_patch)
        if quota_patch:
            quotas = dataclasses.replace(quotas, **quota_patch)
        gangs = self._dev_gangs() if "gang_min" in derived else snap.gangs
        return ClusterSnapshot(
            nodes=nodes, pods=pods, gangs=gangs, quotas=quotas,
            throughput=throughput,
        )

    # -- resident score tensors (ISSUE 9) --
    def score_residency(self) -> Optional[ScoreResidency]:
        """The resident [P, N] score/feasible tensors with their
        accumulated dirt, or None (never scored, or residency dropped).
        Callers serialize through the dispatch launch lock: commits
        mutate the dirt under it (run_exclusive) and Score launches
        read/advance under it."""
        return self._score_res

    def drop_score_residency(self) -> None:
        self._score_res = None

    def store_score_result(self, cfg, scores, feasible) -> None:
        """Adopt the tensors a Score launch just certified: the
        residency's dirt clears (the launch incorporated it) and the
        tensors land in the canonical placement — node-axis-sharded
        over the cluster mesh when mesh-resident
        (parallel/mesh.py ``score_sharding``), so the next incremental
        rescore partitions without any resharding program.  device_put
        with an already-matching sharding is a no-op, which is exactly
        the incremental path's case (the shard_map preserves specs)."""
        mesh = self.active_mesh()
        if mesh is not None:
            from koordinator_tpu.parallel.mesh import score_sharding

            spec = score_sharding(mesh)
            scores = jax.device_put(scores, spec)
            feasible = jax.device_put(feasible, spec)
        self._score_res = ScoreResidency(cfg, scores, feasible)

    def _note_score_dirty(self, score_dirty) -> None:
        """Advance the score residency past a warm commit: union the
        invalidated rows (None = attribution lost, e.g. a full-tensor
        re-upload — the residency drops and the next Score full-
        rescores)."""
        res = self._score_res
        if res is None:
            return
        if score_dirty is None:
            self._score_res = None
            return
        dirty_nodes, dirty_pods = score_dirty
        res.dirty_nodes |= dirty_nodes
        res.dirty_pods |= dirty_pods

    # -- resident sparse candidate lists (ISSUE 16) --
    def candidate_residency(self) -> Optional[CandidateResidency]:
        """The resident [P, C] candidate-index map with its exact
        per-pod feasible counts and accumulated dirt, or None (never
        built, or dropped).  Same serialization contract as
        :meth:`score_residency`: commits mutate the dirt under the
        dispatch launch lock and sparse Score launches read/advance
        under it."""
        return self._cand_res

    def drop_candidate_residency(self) -> None:
        self._cand_res = None

    def store_candidates(self, cfg, idx, count, merges: int = 0) -> None:
        """Adopt the candidate lists a sparse Score launch just built
        or refreshed: the dirt clears (the launch incorporated it) and
        ``merges`` records how deep the merge-refresh chain has grown
        since the last full build (0 after a cold/stale rebuild).
        Stored unsharded: the serving path runs the GSPMD-compatible
        unsharded functions regardless of node-mesh residency — the
        pod-mesh shard_map variants are exercised through
        solver/candidates.py's explicit ``mesh=`` parameter."""
        self._cand_res = CandidateResidency(cfg, idx, count, merges=merges)

    def _note_candidate_dirty(self, score_dirty) -> None:
        """Advance the candidate residency past a warm commit with the
        SAME row attribution the score residency uses — a conservative
        superset for feasibility (which reads fewer tensors than
        scoring), so the extra merge-refreshes are harmless and the
        lists stay exact.  None = attribution lost: drop, the next
        sparse Score cold-rebuilds."""
        res = self._cand_res
        if res is None:
            return
        if score_dirty is None:
            self._cand_res = None
            return
        dirty_nodes, dirty_pods = score_dirty
        res.dirty_nodes |= dirty_nodes
        res.dirty_pods |= dirty_pods

    def _score_dirty_rows(self, staged, plan):
        """(dirty node rows, dirty pod rows) a warm plan invalidates in
        the resident score tensors, or None when row attribution is
        lost (a full tensor rode the frame).  Runs BEFORE the mirror
        commit — derived-column comparisons need the old values.

        Row attribution per update kind:

        * a sparse delta's flat indices divide by the mirror's trailing
          row size — the same index space the device scatter targets;
        * quota tensors contribute nothing (``_SCORE_NODE_TENSORS``
          note: score_cycle never reads quota state);
        * derived freshness (``node_fresh``) diffs old-vs-new per node
          (None means the all-fresh default, the ``_dev_metric_fresh``
          rule);
        * priority/priority-class changes dirty the pods whose
          EFFECTIVE class moved — the one column score_cycle reads
          (``_pc_column``, the same derivation the device builder
          uses); raw priority feeds scoring only through it.
        """
        tensor_updates, derived = plan
        dirty_nodes: set = set()
        dirty_pods: set = set()
        for key, update in tensor_updates.items():
            if key == "pod_estimated_from_requests":
                continue  # rides pod_requests' indices, counted there
            if key == "term_throughput":
                # a change to matrix cell (c, a) invalidates the score
                # columns of every node whose CLIPPED accel type is a
                # (the gather clips, so out-of-range types alias the
                # edge rows) — matched against the post-commit accel
                # column, since that is what the next gather reads; an
                # accel flip in the SAME frame dirties its own rows
                # through the derived diff below.  Unlike the row-major
                # snapshot tensors, a FULL re-upload stays attributable:
                # the matrix is tiny ([C, A]) and warm-plan geometry is
                # unchanged, so the exact changed-cell set is one cheap
                # mirror diff (the delta ratio gate routinely ships
                # small matrices full — dropping residency for that
                # would make every trace-realistic throughput event a
                # full rescore).
                tput = np.asarray(self.term_throughput, np.int64)
                if update[0] == "delta":
                    changed = np.asarray(update[1], np.int64)
                else:
                    changed = np.flatnonzero(
                        tput.reshape(-1)
                        != np.asarray(staged[key], np.int64).reshape(-1)
                    )
                A = int(tput.shape[-1]) if tput.ndim > 1 else 1
                touched = set((changed % A).tolist())
                N = self.node_alloc.shape[0]
                accel_new = staged.get("node_accel", self.node_accel)
                accel = (
                    np.asarray(accel_new, np.int64)
                    if accel_new is not None
                    else np.zeros(N, np.int64)
                )
                accel = np.clip(accel[:N], 0, A - 1)
                for a in touched:
                    dirty_nodes.update(np.flatnonzero(accel == a).tolist())
                continue
            if key not in _SCORE_NODE_TENSORS and key not in _SCORE_POD_TENSORS:
                continue
            if update[0] != "delta":
                return None  # full re-upload: which rows moved is unknown
            base = np.asarray(getattr(self, key))
            trailing = int(np.prod(base.shape[1:])) if base.ndim > 1 else 1
            rows = dirty_nodes if key in _SCORE_NODE_TENSORS else dirty_pods
            rows.update(
                (np.asarray(update[1], np.int64) // trailing).tolist()
            )
        # gate on the plan's derived set: _warm_plan already diffed the
        # scalar columns, so an unchanged list riding the frame costs
        # nothing here while the launch lock holds back Score/Assign
        new_fresh = staged.get("node_fresh")
        if "node_fresh" in derived and new_fresh is not None:
            new_fresh = np.asarray(new_fresh, bool)
            old_fresh = (
                np.asarray(self.node_fresh, bool)
                if self.node_fresh is not None
                else np.ones(len(new_fresh), bool)
            )
            if len(old_fresh) == len(new_fresh):
                dirty_nodes.update(
                    np.flatnonzero(old_fresh != new_fresh).tolist()
                )
            else:
                return None  # length moved without a resize: stay safe
        if "pod_priority" in derived or "pod_priority_class" in derived:
            P = self.pod_requests.shape[0]

            def eff_class(explicit, priority):
                prio = (
                    np.asarray(priority)
                    if priority is not None
                    else np.zeros(P, np.int64)
                )
                return _pc_column(explicit, prio, P, P)

            old_cls = eff_class(self.pod_priority_class, self.pod_priority)
            new_cls = eff_class(
                staged.get("pod_priority_class", self.pod_priority_class),
                staged.get("pod_priority", self.pod_priority),
            )
            dirty_pods.update(np.flatnonzero(old_cls != new_cls).tolist())
        # term columns (ISSUE 15): an accel-type flip moves that node's
        # heterogeneity gather (dirty column), a workload-class flip
        # moves that pod's row.  First appearance went cold in
        # _warm_plan, so old is always an array here; length moved
        # without a resize = stay safe, like the freshness rule.
        for key, rows in (("node_accel", dirty_nodes),
                          ("pod_workload", dirty_pods)):
            if key not in derived:
                continue
            new_col = staged.get(key)
            old_col = getattr(self, key)
            if new_col is None or old_col is None:
                return None
            new_col = np.asarray(new_col, np.int64)
            old_col = np.asarray(old_col, np.int64)
            if len(new_col) != len(old_col):
                return None
            rows.update(np.flatnonzero(old_col != new_col).tolist())
        return dirty_nodes, dirty_pods

    def i32_fits(self) -> bool:
        """Whether the resident tensors fit the Pallas kernel's i32
        arithmetic — computed from the host-side numpy mirrors so the
        per-cycle device round-trip in solver.pallas_inputs_fit_i32 is
        skipped on the Assign hot path."""
        if self._i32_ok is None:
            from koordinator_tpu.solver import check_i32_bounds

            zeros = np.zeros(1, np.int64)

            def amax(a):
                return int(np.abs(a).max()) if a is not None and a.size else 0

            est = (
                self.pod_estimated
                if self.pod_estimated is not None
                else self.pod_requests
            )
            scored = max(
                amax(self.node_alloc),
                amax(self.node_requested),
                amax(self.node_usage),
                amax(self.pod_requests),
                amax(est),
            )
            quota = max(amax(self.quota_runtime), amax(self.quota_used))
            est_sum = int(
                np.abs(est if est is not None else zeros).sum(axis=0).max()
            )
            req_sum = int(np.abs(self.pod_requests).sum(axis=0).max())
            self._i32_ok = check_i32_bounds((scored, quota, est_sum, req_sum))
        return self._i32_ok

    def _pad2(self, a: np.ndarray, rows: int) -> np.ndarray:
        out = np.zeros((rows, a.shape[1]), np.int64)
        out[: a.shape[0]] = a
        return out

    # -- mesh placement (ISSUE 7) --
    def active_mesh(self):
        """The cluster mesh for the CURRENT node bucket, or None (no
        mesh configured, or the bucket does not divide over it — then
        the snapshot stays single-chip for this geometry, logged once).
        Buckets are powers of two, so power-of-two device prefixes
        always divide."""
        if self.mesh is None:
            return None
        nb = self.node_bucket
        if nb and nb % self.mesh.size == 0:
            return self.mesh
        if nb and nb not in self._mesh_skip_warned:
            self._mesh_skip_warned.add(nb)
            logger.warning(
                "node bucket %d does not divide over the %d-device "
                "cluster mesh; resident snapshot stays single-chip for "
                "this geometry",
                nb, self.mesh.size,
            )
        return None

    def _place_node(self, a):
        """Place a node-major tensor: sharded along the cluster mesh's
        node axis when mesh-resident, plain device array otherwise."""
        m = self.active_mesh()
        if m is None:
            return jnp.asarray(a)
        from koordinator_tpu.parallel.mesh import node_sharding

        return jax.device_put(a, node_sharding(m, np.ndim(a)))

    def _place_rep(self, a):
        """Place a pod/gang/quota tensor: replicated over the cluster
        mesh when mesh-resident (the wave certifier and the quota
        admission recheck read them on every shard)."""
        m = self.active_mesh()
        if m is None:
            return jnp.asarray(a)
        from koordinator_tpu.parallel.mesh import replicated_sharding

        return jax.device_put(a, replicated_sharding(m))

    # -- per-field device builders (shared by cold rebuild + warm patch;
    #    one implementation keeps the two paths bit-exact) --
    def _dev_padded2(self, key: str, rows: int) -> jnp.ndarray:
        place = self._place_node if key.startswith("node_") else self._place_rep
        return place(
            self._pad2(np.asarray(getattr(self, key), np.int64), rows)
        )

    def _dev_metric_fresh(self) -> jnp.ndarray:
        N = self.node_alloc.shape[0]
        fresh = np.zeros(self.node_bucket, bool)
        fresh[:N] = (
            self.node_fresh if self.node_fresh is not None else np.ones(N, bool)
        )
        return self._place_node(fresh)

    def _dev_agg_usage(self):
        if not _present(self.node_agg):
            return None
        return self._place_node(_pad_rows_to(self.node_agg, self.node_bucket))

    def _dev_agg_fresh(self):
        if not _present(self.node_agg_fresh):
            return None
        return self._place_node(
            _pad_rows_to(self.node_agg_fresh, self.node_bucket).astype(bool)
        )

    def _dev_prod_usage(self):
        if not _present(self.node_prod):
            return None
        return self._place_node(
            _pad_rows_to(np.asarray(self.node_prod, np.int64), self.node_bucket)
        )

    def _dev_accel_type(self) -> jnp.ndarray:
        """Node accel-type column padded to the bucket (pad rows type 0
        — padded nodes are masked by ``valid`` everywhere, and the term
        gather clips, so the pad value is inert)."""
        N = self.node_alloc.shape[0]
        col = np.zeros(self.node_bucket, np.int32)
        if self.node_accel is not None:
            src = np.asarray(self.node_accel, np.int32)
            col[: min(N, len(src))] = src[:N]
        return self._place_node(col)

    def _dev_workload_class(self) -> jnp.ndarray:
        P = self.pod_requests.shape[0]
        col = np.zeros(self.pod_bucket, np.int32)
        if self.pod_workload is not None:
            src = np.asarray(self.pod_workload, np.int32)
            col[: min(P, len(src))] = src[:P]
        return self._place_rep(col)

    def _dev_sensitivity(self) -> jnp.ndarray:
        return self._place_rep(
            self._pad2(
                np.asarray(self.pod_sensitivity, np.int64), self.pod_bucket
            )
        )

    def _dev_throughput(self) -> jnp.ndarray:
        """The [C, A] throughput matrix: replicated, never padded (its
        geometry is per-(class, accel), not per-row)."""
        return self._place_rep(np.asarray(self.term_throughput, np.int64))

    def _dev_estimated(self) -> jnp.ndarray:
        est = (
            self.pod_estimated
            if self.pod_estimated is not None
            else self.pod_requests
        )
        return self._place_rep(self._pad2(np.asarray(est, np.int64), self.pod_bucket))

    def _dev_priority(self) -> jnp.ndarray:
        P = self.pod_requests.shape[0]
        prio = (
            self.pod_priority
            if self.pod_priority is not None
            else np.zeros(P, np.int64)
        )
        pprio = np.zeros(self.pod_bucket, np.int64)
        pprio[:P] = prio
        return self._place_rep(pprio)

    def _dev_priority_class(self) -> jnp.ndarray:
        P = self.pod_requests.shape[0]
        prio = (
            self.pod_priority
            if self.pod_priority is not None
            else np.zeros(P, np.int64)
        )
        # explicit classes from the wire, else derived from the priority
        # value bands (apis/extension/priority.go:84); padding is NONE —
        # zeros would mean PROD and wrongly put padded pods on the prod
        # filter/score path
        return self._place_rep(
            _pc_column(self.pod_priority_class, prio, P, self.pod_bucket)
        )

    def _dev_gang_id(self) -> jnp.ndarray:
        P = self.pod_requests.shape[0]
        gang = (
            self.pod_gang if self.pod_gang is not None else np.full(P, -1, np.int32)
        )
        pgang = np.full(self.pod_bucket, -1, np.int32)
        pgang[:P] = gang
        return self._place_rep(pgang)

    def _dev_quota_id(self) -> jnp.ndarray:
        P = self.pod_requests.shape[0]
        quota = (
            self.pod_quota if self.pod_quota is not None else np.full(P, -1, np.int32)
        )
        pquota = np.full(self.pod_bucket, -1, np.int32)
        pquota[:P] = quota
        return self._place_rep(pquota)

    def _dev_gangs(self) -> GangTable:
        gmin = self.gang_min if self.gang_min is not None else np.zeros(0, np.int32)
        G = max(1, len(gmin))
        gvalid = np.zeros(G, bool)
        gvalid[: len(gmin)] = True
        gm = np.zeros(G, np.int32)
        gm[: len(gmin)] = gmin
        return GangTable(
            min_member=self._place_rep(gm),
            valid=self._place_rep(gvalid),
            names=(),
        )

    def snapshot(self) -> ClusterSnapshot:
        if self._snapshot is not None:
            return self._snapshot
        N = self.node_alloc.shape[0]
        P = self.pod_requests.shape[0]
        nb, pb = self.node_bucket, self.pod_bucket
        nvalid = np.zeros(nb, bool)
        nvalid[:N] = True
        pvalid = np.zeros(pb, bool)
        pvalid[:P] = True
        if _present(self.quota_runtime):
            Q = self.quota_runtime.shape[0]
            qrt, quse = self.quota_runtime, self.quota_used
            qlim = self.quota_limited.astype(bool)
            qvalid = np.ones(Q, bool)
        else:
            qrt = np.zeros((1, R), np.int64)
            quse = np.zeros((1, R), np.int64)
            qlim = np.zeros((1, R), bool)
            qvalid = np.zeros(1, bool)

        self._snapshot = ClusterSnapshot(
            nodes=NodeBatch(
                allocatable=self._dev_padded2("node_alloc", nb),
                requested=(
                    self._dev_padded2("node_requested", nb)
                    if self.node_requested is not None
                    else self._place_node(np.zeros((nb, R), np.int64))
                ),
                usage=(
                    self._dev_padded2("node_usage", nb)
                    if self.node_usage is not None
                    else self._place_node(np.zeros((nb, R), np.int64))
                ),
                metric_fresh=self._dev_metric_fresh(),
                valid=self._place_node(nvalid),
                agg_usage=self._dev_agg_usage(),
                agg_fresh=self._dev_agg_fresh(),
                prod_usage=self._dev_prod_usage(),
                accel_type=(
                    self._dev_accel_type()
                    if self.node_accel is not None
                    else None
                ),
                names=(),
            ),
            pods=PodBatch(
                requests=self._dev_padded2("pod_requests", pb),
                estimated=self._dev_estimated(),
                priority_class=self._dev_priority_class(),
                qos=self._place_rep(np.zeros(pb, np.int32)),
                priority=self._dev_priority(),
                gang_id=self._dev_gang_id(),
                quota_id=self._dev_quota_id(),
                valid=self._place_rep(pvalid),
                workload_class=(
                    self._dev_workload_class()
                    if self.pod_workload is not None
                    else None
                ),
                sensitivity=(
                    self._dev_sensitivity()
                    if _present(self.pod_sensitivity)
                    else None
                ),
                names=(),
            ),
            gangs=self._dev_gangs(),
            quotas=QuotaTable(
                runtime=self._place_rep(qrt),
                used=self._place_rep(quse),
                limited=self._place_rep(qlim),
                valid=self._place_rep(qvalid),
                names=(),
            ),
            throughput=(
                self._dev_throughput()
                if _present(self.term_throughput)
                else None
            ),
        )
        return self._snapshot
