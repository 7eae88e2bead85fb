"""BatchedScorer gRPC service (generic handlers; no grpcio-tools needed).

The service composition mirrors the reference's hook-server dispatch
(reference ``pkg/koordlet/runtimehooks/proxyserver``): one process owns
the device, callers talk UDS.  Score/Assign run the same device programs
as the in-process API (solver.run_cycle / solver.score_cycle), so bridge
clients get identical placements to embedded users.

Concurrency (ISSUE 5 coalescing + ISSUE 6 pipelining; docs/PIPELINE.md
has the full picture).  The pre-PR-5 daemon held ONE lock across every
RPC body; PR 5 split it three ways and coalesced concurrent Scores into
shared batched launches; PR 6 made the device section a depth-2
pipeline:

* ``_sync_lock`` serializes Sync RPCs and pins the mirror baseline for
  the protobuf->numpy decode — which runs OUTSIDE the device critical
  section, so decode of Sync k+1 overlaps the (async) on-device delta
  scatter of cycle k (a depth-2 decode/scatter pipeline).
* ``_state_lock`` guards the resident mirrors, the generation counter,
  the Assign result memo and telemetry sequencing.  It is never held
  across a device dispatch or a blocking readback (koordlint's
  ``lock-held-dispatch`` rule rejects that statically).
* the **pipelined dispatch queue** (bridge/coalesce.py): the launch
  critical section covers only snapshot capture + async device
  dispatch; the blocking stacked readback and the numpy demux run OFF
  the launch lock, so batch k+1 launches while batch k's transfer is
  still in flight (double buffering — the device never idles between
  coalesced launches).  A warm Sync's donating delta scatter drains
  the pipeline first (``run_exclusive(drain=True)``) so a donation can
  never invalidate a buffer an in-flight batch has not read back;
  non-donating commits keep the pipeline flowing.

Concurrent Assigns against the SAME resident snapshot re-ran identical
device cycles under PR 5; they are now served from a result memo keyed
on (snapshot id, CycleConfig), invalidated atomically with every
generation bump — one cycle runs, its certified result fans out, and
the replies are bit-identical to serial execution (timing fields aside).

Warm Scores run an ENGINE LADDER (ISSUE 9): the (snapshot id, config,
k-bucket) prefix memo first (no device work at all), then the
incremental column/row rescore of the device-resident [P, N]
score/feasible tensors (only what the delta Syncs since the last
launch dirtied — solver/incremental.py, bit-identical by
construction), then the full ``score_cycle``.  The resident tensors
advance with every generation bump instead of being discarded; cold
Syncs, geometry changes, full-tensor re-uploads, a CycleConfig change
or a dirty ratio past ``--score-incr-max-ratio`` fall back to the
full rescore (docs/KERNEL.md "Incremental scoring" has the matrix).

The wire contract is untouched: replies are byte-identical to the
serialized daemon's, only the internal concurrency changed.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from concurrent import futures
from typing import List, Optional

import numpy as np

import grpc
import jax

from koordinator_tpu.bridge.codegen import SERVICE, pb2
from koordinator_tpu.bridge.coalesce import (
    AdaptiveGatherWindow,
    CoalescingDispatcher,
    DEFAULT_DEPTH,
    DeadlineExpired,
    PendingRequest,
    ScoreMemo,
    SnapshotNotResident,
    launch_section,
)
from koordinator_tpu.bridge.state import ResidentState
from koordinator_tpu.config import CycleConfig, DEFAULT_CYCLE_CONFIG
from koordinator_tpu.model.snapshot import pad_bucket
from koordinator_tpu.obs import CycleTelemetry
from koordinator_tpu.obs import lockwitness
from koordinator_tpu.obs.lockwitness import witness_lock
from koordinator_tpu.obs.spans import profiled, tag_cycle
from koordinator_tpu.replication.admission import (
    AdmissionGate,
    BreakerOpen,
    CircuitBreaker,
    ResourceExhausted,
)
from koordinator_tpu.solver import (
    STATUS_WAIT_GANG,
    CandidateOverflow,
    build_candidates,
    masked_top_k,
    refresh_candidates,
    run_cycle,
    score_candidates,
    score_cycle,
    score_upper_bound,
    sparse_top_k,
)
from koordinator_tpu.solver.candidates import check_candidate_overflow


class _AssignMemo:
    """One (snapshot id, CycleConfig)'s certified Assign result.

    The owner (first RPC to miss) runs the device cycle and publishes
    under the servicer's ``_state_lock``; waiters block on ``done``
    OUTSIDE every lock.  ``result`` is a host-side tuple — the memo
    never pins device buffers, so it cannot interact with donation.
    A generation bump clears the memo dict atomically (same
    ``_state_lock`` hold that bumps), but an entry already handed to a
    waiter stays valid: that waiter passed its generation check before
    the bump, which is exactly the serial schedule where its Assign ran
    first."""

    __slots__ = ("done", "result", "error", "span_ref")

    def __init__(self):
        self.done = threading.Event()
        # (assignment, status, valid, path, rounds, eff_wave, cycle_ms)
        self.result = None
        self.error: Optional[BaseException] = None
        # the owner's (trace_id, span_id) when its RPC was traced
        # (ISSUE 14): memo-served Assigns fan-in link to the span that
        # certified the shared result
        self.span_ref = None


class ScorerServicer:
    def __init__(
        self,
        cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
        mesh=None,
        state_dir=None,
        telemetry: Optional[CycleTelemetry] = None,
        coalesce_max_batch: int = 16,
        coalesce_window_ms: Optional[float] = None,
        pipeline_depth: int = DEFAULT_DEPTH,
        mesh_resident: bool = False,
        coalesce_cap_ms: Optional[float] = None,
        score_memo: bool = True,
        max_inflight: int = 0,
        score_incr: bool = True,
        score_incr_max_ratio: Optional[float] = None,
        breaker_threshold: Optional[int] = None,
        breaker_cooldown_ms: Optional[float] = None,
        brownout_max_lag: Optional[int] = None,
        trace_export: Optional[str] = None,
        shed_fractions=None,
    ):
        """``mesh``: a ``jax.sharding.Mesh`` turns the ASSIGN RPC into
        the round-based multi-chip cycle (parallel/shard_assign.py
        greedy_assign_waves, bit-identical with the single-chip path);
        clients see ``path="shard"``.  By default the mesh buys cycle
        wall-clock only — Sync and Score still materialize the snapshot
        on the default device, so the resident tensors must fit one
        chip's memory.  A shard-path failure falls back to the
        single-chip cycle for that RPC (placements are bit-identical
        either way).

        ``mesh_resident`` (ISSUE 7): the SNAPSHOT ITSELF lives sharded
        over ``mesh`` — node tensors split along the mesh's node axis
        (the combined HBM is the cluster's capacity), pod rows and the
        gang/quota tables replicate, warm delta Syncs scatter into the
        owning shard only, and Score/Assign launch against the sharded
        tensors through the same pipelined dispatch seam (only each
        caller's top-k prefix is ever gathered to host).  Pass the 1-D
        ``parallel.cluster_mesh`` here; placements stay bit-identical
        to the single-chip oracle (the cross-shard top-M merge reuses
        the packed-key tie-break).

        ``score_memo``: memoize each (snapshot id, CycleConfig,
        k-bucket) Score readback so a Score storm against an unchanged
        snapshot serves sliced prefixes from ONE launch
        (bridge/coalesce.py ScoreMemo; invalidated atomically on every
        generation bump, the Assign-memo contract).  ``False`` disables
        it — the bench storms do, to keep measuring the dispatch
        engine itself.

        ``score_incr`` (ISSUE 9, the tentpole): keep the [P, N]
        score/feasible tensors device-resident across generations and
        serve a warm Score by rescoring ONLY the columns/rows the
        delta Syncs since the last launch dirtied
        (solver/incremental.py; O(P x d) + O(d_p x N) instead of
        O(P x N), bit-identical by the gather/scatter exactness
        contract).  Engine order per Score batch: prefix memo ->
        incremental -> full rescore.  ``False`` disables it — the
        bench storms do, mirroring ``score_memo=False``, so they keep
        measuring the dispatch engines rather than the short-circuit.

        ``score_incr_max_ratio``: dirty-cost fraction
        (``d_nodes/N + d_pods/P`` — the incremental arithmetic
        relative to a full rescore) above which a Score falls back to
        the full ``score_cycle`` (rescoring most of the tensor
        incrementally costs MORE: two scatters plus worse fusion).
        Default from ``KOORD_SCORE_INCR_MAX_RATIO``, else 0.5 — tuned
        with the trace harness against realistic delta-size mixes
        (ISSUE 13 / ROADMAP 5(b), docs/KERNEL.md "Tuning the ratio
        gate": the incr/full crossover measures at ~0.6 dirty
        fraction, and the old 0.25 default was refusing the 1.7-3x
        wins in the 0.25-0.5 band that usage-drift events actually
        produce); daemon flag ``--score-incr-max-ratio``.

        ``coalesce_cap_ms``: clamp of the adaptive gather window's
        straggler wait (AdaptiveGatherWindow cap_ms; default 5.0) —
        a daemon flag since ISSUE 7 so real-TPU tuning rounds need no
        code edits.  Ignored when ``coalesce_window_ms`` pins a static
        window.

        ``state_dir``: where flight-recorder dumps land (obs/flight.py;
        the daemon passes its --state-dir).  ``telemetry`` injects a
        pre-built CycleTelemetry (tests); by default one is created with
        this servicer's epoch so cycle ids ("c<epoch>-<seq>") correlate
        with snapshot ids ("s<epoch>-<gen>").

        ``max_inflight`` (ISSUE 8 admission control): read RPCs
        (Score/Assign) admitted-but-unfinished at once before new ones
        are shed with RESOURCE_EXHAUSTED + a retry-after hint
        (replication/admission.py; daemon flag ``--max-inflight`` /
        ``KOORD_MAX_INFLIGHT``).  0 (the default) disables the gate.
        Sync is never shed — the one-writer path must not degrade
        under a read storm.

        ``breaker_threshold`` / ``breaker_cooldown_ms`` (ISSUE 13):
        consecutive launch failures that trip the circuit breaker OPEN,
        and how long it stays open before admitting one half-open
        probe.  While open, Score degrades to the brownout cache
        (below) and Assign fails fast with UNAVAILABLE + retry-after
        instead of queueing behind a failing device.  Defaults from
        ``KOORD_BREAKER_THRESHOLD`` (3; 0 disables the breaker) and
        ``KOORD_BREAKER_COOLDOWN_MS`` (250); daemon flags
        ``--breaker-threshold`` / ``--breaker-cooldown-ms``.  Admission
        sheds and request-level rejections (stale snapshot, expired
        deadline) never feed the breaker.

        ``trace_export`` (ISSUE 14, distributed tracing): directory the
        span exporter appends OTLP-shaped JSON lines to ("1"/"true" =
        the default ``<state-dir>/traces``; None falls back to the
        ``KOORD_TRACE_EXPORT`` env).  Tracing itself is request-driven:
        a request carrying ``trace_id`` gets a server span (parented
        under the client's attempt span, echoed as ``server_span`` in
        the reply) whether or not an exporter persists it — an
        untraced request pays one string check.  Coalesced batches mint
        ONE launch span; every rider's RPC span fan-in links to it, as
        do memo and brownout serves to the launch that produced their
        cached bytes (docs/OBSERVABILITY.md "Distributed tracing").

        ``shed_fractions`` (ISSUE 14 satellite): per-band shed ladder
        overrides for the admission gate (``--shed-fraction-<band>`` /
        ``KOORD_SHED_FRACTION_*``; validated monotone across bands and
        in (0, 1] — replication/admission.py).

        ``brownout_max_lag`` (ISSUE 13): maximum generations behind the
        current snapshot a breaker-open Score may be served from the
        host-side brownout cache (the last launch's padded top-k
        readback).  A reply within the bound carries an explicit
        ``degraded`` flag; one past it is REFUSED (UNAVAILABLE +
        retry-after), never served — stale-but-bounded, by contract.
        Default from ``KOORD_BROWNOUT_MAX_LAG`` (2); daemon flag
        ``--brownout-max-lag``.  Assign never serves stale.

        ``coalesce_max_batch``: Score requests sharing one device launch
        at most (1 = the pre-coalescing serialized behavior, the bench
        baseline).  ``coalesce_window_ms``: ``None`` (the default)
        derives the gather window adaptively from the observed
        inter-arrival EWMA (bridge/coalesce.py AdaptiveGatherWindow —
        lone requests keep serial latency, burst trains converge onto
        wide batches); a float pins the ISSUE-5 static window (0 = never
        wait).  ``pipeline_depth``: launched-but-unread batches allowed
        in flight (2 = double buffering; 1 = the ISSUE-5 serial-readback
        engine, the pipeline bench baseline)."""
        self.cfg = cfg
        self.mesh = mesh
        self.mesh_resident = bool(mesh_resident and mesh is not None)
        self.state = ResidentState(mesh=mesh if self.mesh_resident else None)
        self._generation = 0
        # per-boot epoch in every snapshot id ("s<epoch>-<gen>"): a client
        # checking bare generation continuity (gen == mirror.gen+1) can
        # coincidentally pass after a sidecar restart reset the counter,
        # and would then delta-sync onto a foreign baseline; the epoch
        # makes the restart unmistakable (ADVICE r5)
        self._epoch = uuid.uuid4().hex[:8]
        self.telemetry = telemetry or CycleTelemetry(
            epoch=self._epoch, cfg=cfg, state_dir=state_dir,
            trace_export=trace_export,
        )
        if lockwitness.enabled():
            # witness mode: distinct observed/inversion edges feed
            # koord_scorer_lock_witness_edges_total (late attach replays)
            lockwitness.attach_metrics(self.telemetry.metrics)
        # the lock split (module docstring): _sync_lock serializes Sync
        # decodes against the mirror baseline; _state_lock guards mirror
        # commits, the generation counter, the Assign memo and telemetry
        # sequencing — and is NEVER held across a device dispatch or
        # blocking readback; the dispatcher's launch lock serializes
        # launches.  Lock order where nesting happens: launch -> state.
        self._sync_lock = witness_lock(
            "bridge.server.ScorerServicer._sync_lock")
        self._state_lock = witness_lock(
            "bridge.server.ScorerServicer._state_lock")
        # Assign result memo: (snapshot id, CycleConfig) -> _AssignMemo,
        # cleared atomically with every generation bump
        self._assign_memo = {}
        # per-thread: an Assign's open koord.assign_queue annotation
        self._rpc_local = threading.local()
        # Score top-k prefix memo (same invalidation; None = disabled)
        self._score_memo = ScoreMemo() if score_memo else None
        # incremental score engine (ISSUE 9): resident [P, N] tensors
        # advanced column-wise by warm deltas; the ratio gates when a
        # mostly-dirty tensor should just full-rescore
        self._score_incr = bool(score_incr)
        if score_incr_max_ratio is None:
            # `or`: an empty env value means unset (every KOORD_* knob's
            # convention), not a float('') crash at daemon startup
            # 0.5: tuned via the trace-harness sweep (ROADMAP 5(b) /
            # docs/KERNEL.md "Tuning the ratio gate") — the measured
            # incr-vs-full crossover sits at ~0.6 dirty fraction
            score_incr_max_ratio = float(
                os.environ.get("KOORD_SCORE_INCR_MAX_RATIO") or "0.5"
            )
        self._score_incr_max_ratio = float(score_incr_max_ratio)
        # admission gate in front of the dispatch queue (ISSUE 8):
        # Score/Assign reserve a slot before touching the coalescer,
        # overload sheds fast instead of queueing without bound
        # (band-aware ladder since ISSUE 13: free sheds first, prod
        # last, Sync never; fractions flag/env-tunable since ISSUE 14)
        self.admission = AdmissionGate(
            max_inflight, shed_fractions=shed_fractions
        )
        # circuit breaker + brownout ladder (ISSUE 13): consecutive
        # launch failures trip the breaker; while open, Score serves
        # stale-but-bounded from the host-side brownout cache and
        # Assign fails fast.  `or`: empty env value means unset (the
        # KOORD_* convention).
        if breaker_threshold is None:
            breaker_threshold = int(
                os.environ.get("KOORD_BREAKER_THRESHOLD") or "3"
            )
        if breaker_cooldown_ms is None:
            breaker_cooldown_ms = float(
                os.environ.get("KOORD_BREAKER_COOLDOWN_MS") or "250"
            )
        if brownout_max_lag is None:
            brownout_max_lag = int(
                os.environ.get("KOORD_BROWNOUT_MAX_LAG") or "2"
            )
        self.breaker = CircuitBreaker(
            threshold=breaker_threshold,
            cooldown_ms=breaker_cooldown_ms,
            on_transition=self._breaker_transition,
        )
        self._brownout_max_lag = max(0, int(brownout_max_lag))
        # ROADMAP 6(a): cache the launch's FULL [P, N] scores readback
        # alongside the padded top-k when the tensor is small enough
        # (cells <= KOORD_BROWNOUT_FULL_CELLS, default 4M = 32 MiB of
        # host i64) — a breaker-open Score wanting a WIDER top-k than
        # the cached launch computed is then ranked on host
        # (solver/topk.py masked_top_k_host, bit-identical) instead of
        # refused.  Past the gate only the padded top-k caches and the
        # wider-k refusal stands — the hot-path transfer cost must not
        # scale with P x N at headline scale.
        self._brownout_full_cells = int(
            os.environ.get("KOORD_BROWNOUT_FULL_CELLS") or str(1 << 22)
        )
        # fused scoring terms (ISSUE 15): enabled term names, counted
        # per device launch on koord_scorer_term_total{term}
        from koordinator_tpu.solver.terms import term_names

        self._term_names = term_names(cfg)
        # host-side brownout cache: the last Score launch's padded
        # top-k readback plus the (epoch, generation, cfg, geometry)
        # it certified.  Unlike the ScoreMemo it deliberately SURVIVES
        # generation bumps — that staleness, bounded by
        # --brownout-max-lag, is exactly what the breaker-open path
        # serves.  Guarded by _state_lock.
        self._brownout = None
        self.degraded_replies = 0  # lifetime stat (bench/tests)
        # replication seam (ISSUE 8): the leader's publisher sets this
        # to stream every committed Sync to the follower tier; called
        # under _sync_lock, so frames publish in generation order
        self.replication_hook = None
        # durability seam (ISSUE 11): the frame journal sets this to
        # append every committed frame's encoded bytes under
        # --state-dir.  Called BEFORE replication_hook (durability
        # first, then fan-out), same _sync_lock ordering guarantee.
        self.journal_hook = None
        self.dispatch = CoalescingDispatcher(
            self._score_launch_batch,
            max_batch=coalesce_max_batch,
            window=(
                AdaptiveGatherWindow(
                    **({} if coalesce_cap_ms is None
                       else {"cap_ms": coalesce_cap_ms})
                )
                if coalesce_window_ms is None else None
            ),
            gather_window_s=(coalesce_window_ms or 0.0) / 1000.0,
            depth=pipeline_depth,
        )
        # degradation-ladder seams into the dispatcher (ISSUE 13):
        # gather-time deadline evictions feed the stage="gather"
        # counter, launch outcomes feed the breaker (filtered below)
        self.dispatch.deadline_hook = self._count_gather_expired
        self.dispatch.launch_outcome_hook = self._launch_outcome
        self.telemetry.metrics.set_breaker_state(self.breaker.state())
        self.telemetry.metrics.set_candidate_width(self.cfg.candidate_width)

    # -- degradation ladder seams (ISSUE 13) --
    def _breaker_transition(self, to: str) -> None:
        self.telemetry.metrics.set_breaker_state(to)
        self.telemetry.metrics.count_breaker_transition(to)

    def _count_gather_expired(self, n: int) -> None:
        self.telemetry.metrics.count_deadline_expired("gather", n)

    def _launch_outcome(self, outcome: str, exc) -> None:
        """The dispatcher's launch-outcome hook -> the breaker.  Only
        REAL launch faults count as failures: request-level rejections
        (a displaced snapshot, an expired deadline) say nothing about
        the device, and a batch that performed no device work releases
        a half-open probe slot without a verdict."""
        if outcome == "ok":
            self.breaker.record_success()
        elif outcome == "error":
            # CandidateOverflow is a config-vs-cluster-state refusal
            # (ISSUE 16: --candidate-width too narrow for the feasible
            # fan-out), not a device fault — tripping the breaker on it
            # would brown out a healthy device
            if isinstance(exc, (SnapshotNotResident, DeadlineExpired,
                                ResourceExhausted, CandidateOverflow)):
                self.breaker.release_probe()
            else:
                self.breaker.record_failure()
        else:  # "none": no device work happened, nothing was probed
            self.breaker.release_probe()

    def _deadline_budget_ms(self, req, ctx) -> float:
        """Effective remaining deadline budget for this RPC in ms: the
        wire field (``deadline_ms``, stamped by the client at send
        time) and the gRPC transport deadline, whichever is tighter.
        0 = no deadline; NEGATIVE = already expired on arrival (the
        stage="queue" rejection).  The raw-UDS framing has no
        transport deadline — the wire field is its only carrier."""
        budget = float(getattr(req, "deadline_ms", 0) or 0)
        if ctx is not None:
            try:
                remaining = ctx.time_remaining()
            except Exception:  # koordlint: disable=broad-except(a transport without deadline support must not fail the RPC; the wire field still applies)
                remaining = None
            if remaining is not None:
                # an exhausted transport deadline must read as expired,
                # not as "no deadline" — never let it round to 0
                remaining_ms = float(remaining) * 1000.0
                if remaining_ms <= 0.0:
                    remaining_ms = -1.0
                if budget <= 0.0:
                    budget = remaining_ms
                else:
                    budget = min(budget, remaining_ms)
        return budget

    def snapshot_id(self) -> str:
        return f"s{self._epoch}-{self._generation}"

    def rebase_epoch(self, epoch: Optional[str] = None) -> str:
        """Mint a fresh epoch while KEEPING the generation (ISSUE 11).
        Used when journal recovery truncated a torn/corrupt tail:
        the truncated frames may already have been published, so
        resuming the identical ``s<epoch>-<gen>`` chain could hand a
        follower/client generation numbers it already holds with
        different content — the one fork the epoch fence cannot see.
        A fresh epoch turns that into the ordinary fenced one-shot
        full resync.  The memos die with the old chain."""
        with self._sync_lock:
            with self._state_lock:
                return self._rebase_epoch_locked(epoch)

    def _rebase_epoch_locked(self, epoch: Optional[str] = None) -> str:
        """The bump itself (``_sync_lock`` + ``_state_lock`` held) —
        shared with FollowerServicer.promote, which composes it with
        its own promoted flag under one lock hold."""
        self._epoch = epoch or uuid.uuid4().hex[:8]
        self._assign_memo.clear()
        if self._score_memo is not None:
            self._score_memo.invalidate()
        return self.snapshot_id()

    def _stale_snapshot(
        self, want: str, sid: Optional[str] = None
    ) -> Optional[SnapshotNotResident]:
        """The ONE stale-snapshot test — serial ``_check_generation`` and
        the coalesced batch's per-entry validation share it, so the
        matching rule and the message can never drift apart.  The FULL id
        must match, epoch included: accepting a bare legacy "s<gen>"
        would re-open for Score/Assign the very restart-coincidence the
        epoch closes (clients echo the Sync reply's id verbatim, so
        nothing legitimate constructs one).  Returns the error to raise,
        or None."""
        sid = self.snapshot_id() if sid is None else sid
        if want and want != sid:
            return SnapshotNotResident(
                f"snapshot {want!r} is not resident (current {sid})"
            )
        return None

    def _check_generation(self, req, ctx) -> None:
        exc = self._stale_snapshot(getattr(req, "snapshot_id", ""))
        if exc is not None:
            if ctx is not None:
                ctx.abort(grpc.StatusCode.FAILED_PRECONDITION, str(exc))
            raise exc

    # -- distributed tracing (ISSUE 14) --
    def _start_rpc_span(self, name: str, req, **attrs):
        """The per-RPC server span, or None when the request carries no
        trace context (the untraced fast path: one truthiness check).
        Parented under the CLIENT's attempt span — the id the wire
        ``parent_span`` field names — so per-process exports assemble
        into one cross-process tree offline (obs/assemble.py)."""
        trace_id = getattr(req, "trace_id", "") or ""
        if not trace_id:
            return None
        return self.telemetry.spans.start_trace_span(
            name, trace_id,
            parent_id=getattr(req, "parent_span", "") or None,
            kind="server", attrs={k: v for k, v in attrs.items() if v},
        )

    # -- RPC bodies (request -> reply functions) --
    def sync(self, req: "pb2.SyncRequest", ctx=None,
             wire_bytes: Optional[bytes] = None) -> "pb2.SyncReply":
        """Tracing shell over :meth:`_sync_impl`: the server span
        covers decode + commit + journal/replication hooks, ends (or
        aborts, error visible) on every exit, and its id rides the
        reply so the client's attempt span can reference it."""
        tspan = self._start_rpc_span("sync", req)
        if tspan is None:
            return self._sync_impl(req, ctx, wire_bytes)
        try:
            reply = self._sync_impl(req, ctx, wire_bytes)
        except BaseException as exc:
            tspan.abort(exc)
            raise
        tspan.set_attr("snapshot_id", reply.snapshot_id)
        reply.server_span = tspan.span_id
        tspan.end()
        return reply

    def _sync_impl(self, req: "pb2.SyncRequest", ctx=None,
                   wire_bytes: Optional[bytes] = None) -> "pb2.SyncReply":
        # Phase 1 under _sync_lock only: the protobuf->numpy decode +
        # validation runs while the device may still be scattering the
        # PREVIOUS sync's deltas (async dispatch) and while coalesced
        # Scores launch — the old single lock serialized all of that.
        # The profiler annotations (obs/spans.py): koord.sync over the
        # body, its cycle stamped once the commit has chosen it;
        # koord.sync_decode before any cycle exists to name
        with profiled("sync") as ann, self._sync_lock:
            t0 = time.perf_counter()
            try:
                with profiled("sync_decode"):
                    staged = self.state.stage_sync(req)
            except Exception as exc:
                # ValueError = a frame validation REJECTED (bad delta
                # shape/index, missing first-sync tensors): the
                # CLIENT's bug, at the client's rate — error counter
                # only.  No flight record, no dump, and crucially no
                # commit of the pending cycle: another client's sync
                # spans may be on it awaiting THEIR Assign, and a
                # looping bad client must be able to churn neither the
                # 64-slot ring nor the dump directory.  Anything else
                # is an unexpected server-side failure: full
                # abort (ring record + disk dump).
                with self._state_lock:
                    if isinstance(exc, ValueError):
                        self.telemetry.metrics.count_cycle_error("sync")
                    else:
                        self.telemetry.abort_cycle("sync", exc)
                raise
            decode_s = time.perf_counter() - t0

            # Phase 2 — atomic commit + the donating device scatter,
            # under launch -> state: the donation must not invalidate
            # buffers an in-flight batch captured but has not read
            # back, and the mirrors/generation/telemetry move together.
            # the cycle this Sync's stages land on, for the annotations
            cycle = [None]

            def commit() -> "pb2.SyncReply":
                with self._state_lock:
                    self.telemetry.flush_backlog()
                    spans = self.telemetry.spans
                    spans.add_measured("sync_decode", decode_s)
                    cycle[0] = spans.current()
                    try:
                        with profiled("sync_apply", cycle[0]):
                            info = self.state.commit_sync(
                                staged, spans=spans, plan=plan_cell[0]
                            )
                    except Exception as exc:
                        self.telemetry.abort_cycle("sync", exc)
                        raise
                    self._generation += 1
                    # the memos die with the generation they certified —
                    # atomically, under the same hold that bumps (an
                    # Assign/Score checking a memo also holds _state_lock)
                    self._assign_memo.clear()
                    if self._score_memo is not None:
                        self._score_memo.invalidate()
                    self.telemetry.record_sync(
                        info,
                        snapshot_id=self.snapshot_id(),
                        epoch=self._epoch,
                        generation=self._generation,
                    )
                    # counts come from the host mirrors.  A warm frame
                    # lands its deltas straight on the resident device
                    # tensors inside commit_sync (state.last_sync_path ==
                    # "warm"); only a cold frame defers the full padded
                    # build to the next Score/Assign
                    return pb2.SyncReply(
                        snapshot_id=self.snapshot_id(),
                        nodes=self.state.node_alloc.shape[0],
                        pods=self.state.pod_requests.shape[0],
                    )

            # the pipeline barrier is donation-scoped: only a warm
            # delta scatter (which donates the pre-delta buffers) must
            # wait for in-flight readbacks; cold/full commits keep the
            # pipeline flowing — in-flight batches hold their own
            # snapshot references, deletion without donation cannot
            # invalidate them.  The decision runs as run_exclusive's
            # drain CALLABLE — i.e. with the launch lock already held:
            # residency only flips inside a launch section (a Score's
            # lazy snapshot() cold rebuild), so a plan computed at the
            # call site could say "cold, no drain" and be warm-with-
            # donation by the time the lock is acquired.  commit()
            # then reuses the very plan the barrier was chosen on.
            plan_cell = [None]

            def _decide_drain() -> bool:
                plan_cell[0] = self.state.plan_commit(staged)
                return self.state.commit_donates(staged, plan=plan_cell[0])

            reply = self.dispatch.run_exclusive(commit, drain=_decide_drain)
            tag_cycle(ann, cycle[0])
            # replication (ISSUE 8): stream the committed frame to the
            # follower tier — still under _sync_lock, so publishes are
            # strictly generation-ordered; the publisher's per-follower
            # queues are non-blocking, so a slow follower can never
            # stall the one writer path
            # ``wire_bytes`` is the CLIENT's original frame when the
            # transport had it in hand (the raw-UDS server always
            # does): the publisher streams those bytes verbatim — no
            # re-encode on the one writer path.  A transport that only
            # has the decoded message (gRPC) passes None and the
            # publisher re-serializes, which is byte-identical (same
            # runtime both ends).
            with profiled("sync_publish", cycle[0]):
                jhook = self.journal_hook
                if jhook is not None:
                    try:
                        jhook(req, reply.snapshot_id, wire_bytes)
                    except Exception:  # the Sync IS committed in memory — a full disk must degrade durability, not fail the acked write; the journal logs and counts the miss
                        import logging

                        logging.getLogger(__name__).exception(
                            "journal append failed for %s",
                            reply.snapshot_id,
                        )
                hook = self.replication_hook
                if hook is not None:
                    try:
                        hook(req, reply.snapshot_id, wire_bytes)
                    except Exception:  # the Sync IS committed — a publisher fault must not fail the client's acked write; followers detect the gap and resync
                        import logging

                        logging.getLogger(__name__).exception(
                            "replication publish failed for %s",
                            reply.snapshot_id,
                        )
            return reply

    # -- replication seam (ISSUE 8; koordinator_tpu/replication/) --
    def export_replication_snapshot(self):
        """``(epoch, generation, payload)`` of the current resident
        state: the kind=full frame a new or resyncing subscriber
        receives.  ``payload`` is the full-state SyncRequest bytes
        (empty before the first Sync — the follower resets to the
        empty state at this generation).  Consistent under
        ``_state_lock``: mirrors and the generation move together at
        commit, so the pair read here is exactly one committed Sync's
        outcome."""
        with self._state_lock:
            epoch, gen = self._epoch, self._generation
            req = self.state.export_sync_request()
        return epoch, gen, (b"" if req is None else req.SerializeToString())

    def apply_replica_frame(self, frame, origin: str = "replica_apply") -> dict:
        """Apply one replication frame (replication/codec.py Frame) and
        adopt the LEADER's ``(epoch, generation)`` — the follower's
        snapshot ids mirror the leader's exactly, so a client holding
        the leader's Sync ack can Score against any caught-up follower.
        Continuity (gap/epoch fencing) is the caller's job
        (replication/follower.py ReplicaApplier); this method only
        applies:

        * a sequence (kind=delta) frame runs the SAME two-phase
          stage/commit seam a client Sync does — delta scatters, warm
          residency, donation barrier and all — so the warm follower
          apply path is the warm leader path, byte for byte;
        * a reset (kind=full) frame swaps in a FRESH ResidentState and
          applies the payload as a first Sync (the one-shot full
          resync).  The swap never donates buffers out of the old
          snapshot, so in-flight read batches keep their references
          and the pipeline keeps flowing (``drain=False``).

        A frame that fails validation raises WITHOUT mutating anything
        (stage-then-commit): the follower keeps serving its last good
        snapshot — never a torn one — and resyncs.

        Distributed tracing (ISSUE 14): a delta frame's payload is the
        client's ORIGINAL SyncRequest bytes, so the originating
        commit's ``trace_id``/``parent_span`` ride it verbatim — this
        apply opens a span in the SAME trace (``origin`` names it:
        "replica_apply" for a live follower frame, "journal_replay"
        for the boot replay), making replication lag and failover gaps
        per-frame measurable in the assembled tree instead of EWMA
        gauges."""
        from koordinator_tpu.replication import codec

        payload = frame.payload
        # an empty payload means two different things by kind: a FULL
        # frame with no bytes resets to the empty pre-first-Sync state
        # (req=None), while a DELTA frame with no bytes is a real
        # no-change client Sync (pb2.SyncRequest() serializes to b"")
        # that must APPLY — forcing a resync for it would replay the
        # full state export on every quiet-cluster Sync
        if frame.kind == codec.KIND_FULL:
            req = pb2.SyncRequest.FromString(payload) if payload else None
        else:
            req = pb2.SyncRequest.FromString(payload)
        aspan = None
        if req is not None and (getattr(req, "trace_id", "") or ""):
            aspan = self.telemetry.spans.start_trace_span(
                origin, req.trace_id,
                parent_id=getattr(req, "parent_span", "") or None,
                kind="consumer",
                attrs={
                    "epoch": frame.epoch,
                    "generation": int(frame.generation),
                    "frame_kind": (
                        "full" if frame.kind == codec.KIND_FULL
                        else "delta"
                    ),
                },
            )
        try:
            with self._sync_lock:
                if frame.kind == codec.KIND_FULL:
                    fresh = ResidentState(mesh=self.state.mesh)
                    staged = None if req is None else fresh.stage_sync(req)

                    def commit_full() -> dict:
                        with self._state_lock:
                            self.state = fresh
                            info = (
                                {"path": "cold", "delta_tensors": 0,
                                 "full_tensors": 0}
                                if staged is None
                                else fresh.commit_sync(staged)
                            )
                            self._adopt_replica_locked(frame, info)
                            return info

                    info = self.dispatch.run_exclusive(
                        commit_full, drain=False
                    )
                else:
                    staged = self.state.stage_sync(req)
                    plan_cell = [None]

                    def commit_seq() -> dict:
                        with self._state_lock:
                            info = self.state.commit_sync(
                                staged, plan=plan_cell[0]
                            )
                            self._adopt_replica_locked(frame, info)
                            return info

                    def _decide_drain() -> bool:
                        plan_cell[0] = self.state.plan_commit(staged)
                        return self.state.commit_donates(
                            staged, plan=plan_cell[0]
                        )

                    info = self.dispatch.run_exclusive(
                        commit_seq, drain=_decide_drain
                    )
        except BaseException as exc:
            # the span must say the apply FAILED (the follower resyncs;
            # the trace shows where the chain broke)
            if aspan is not None:
                aspan.abort(exc)
            raise
        if aspan is not None:
            aspan.set_attr("snapshot_id", self.snapshot_id())
            aspan.end()
        return info

    def _adopt_replica_locked(self, frame, info) -> None:
        """Adopt the leader's snapshot id after a replica apply
        (``_state_lock`` held): generation AND epoch move to the
        frame's, and the memos die exactly as on a client Sync — they
        certified the previous generation."""
        self._epoch = frame.epoch
        self._generation = frame.generation
        self._assign_memo.clear()
        if self._score_memo is not None:
            self._score_memo.invalidate()
        # same backlog valve as a client Sync: a follower applying an
        # endless frame stream with no Assign to correlate must commit
        # span backlog instead of growing one immortal pending cycle
        self.telemetry.flush_backlog()
        self.telemetry.record_sync(
            info,
            snapshot_id=self.snapshot_id(),
            epoch=frame.epoch,
            generation=frame.generation,
        )

    def score(self, req: "pb2.ScoreRequest", ctx=None) -> "pb2.ScoreReply":
        """Tracing shell over :meth:`_score_impl` (see :meth:`sync`);
        the span's error status makes a shed / expired-deadline /
        breaker fast-fail visible in the assembled tree, not just in
        counters."""
        tspan = self._start_rpc_span(
            "score", req,
            band=getattr(req, "band", "") or "",
            top_k=int(getattr(req, "top_k", 0) or 0),
        )
        if tspan is None:
            return self._score_impl(req, ctx, None)
        try:
            reply = self._score_impl(req, ctx, tspan)
        except BaseException as exc:
            tspan.abort(exc)
            raise
        if reply.degraded:
            tspan.set_attr("degraded", True)
        reply.server_span = tspan.span_id
        tspan.end()
        return reply

    def _score_impl(self, req: "pb2.ScoreRequest", ctx=None,
                    tspan=None) -> "pb2.ScoreReply":
        # the degradation ladder, in rung order (ISSUE 13 /
        # docs/REPLICATION.md "Degradation ladder"):
        #   1. admission sheds BEFORE the request can deepen the
        #      dispatch queue — band-aware since ISSUE 13 (free sheds
        #      first, prod last); everything admitted completes
        #   2. an already-exhausted deadline budget is refused here
        #      (stage="queue") — it must never cost a device launch
        #   3. an open breaker serves the brownout cache (bounded
        #      staleness, explicit degraded flag) or fails fast
        #   4. the coalescer evicts entries whose budget expires while
        #      queued (stage="gather") before they occupy a launch slot
        band = getattr(req, "band", "") or ""
        try:
            gate = self.admission.admit("score", band)
            gate.__enter__()
        except ResourceExhausted as exc:
            self.telemetry.metrics.count_shed("score", band)
            if ctx is not None:
                ctx.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc))
            raise
        try:
            budget = self._deadline_budget_ms(req, ctx)
            if budget < 0.0:
                self.telemetry.metrics.count_deadline_expired("queue")
                exc = DeadlineExpired("score", "queue", budget)
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(exc))
                raise exc
            if not self.breaker.allow_launch():
                reply = self._serve_brownout(req, tspan)
                if reply is not None:
                    return reply
                self.telemetry.metrics.count_breaker_rejected("score")
                exc = BreakerOpen(
                    "score", self.breaker.retry_after_ms(),
                    "brownout cache cannot serve within "
                    f"--brownout-max-lag={self._brownout_max_lag}",
                )
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.UNAVAILABLE, str(exc))
                raise exc
            deadline_at = (
                time.perf_counter() + budget / 1000.0
                if budget > 0.0 else None
            )
            # the coalescer runs the batch in whichever caller leads;
            # this caller's slot carries its reply or its error back
            try:
                entry = self.dispatch.submit(
                    req, deadline_at=deadline_at, budget_ms=budget,
                    trace_span=tspan,
                )
            except SnapshotNotResident as exc:
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.FAILED_PRECONDITION, str(exc))
                raise
            except CandidateOverflow as exc:
                # sparse engine refusal (ISSUE 16): the configured
                # --candidate-width cannot hold every feasible node for
                # some pod — refusing beats silently serving a
                # truncated candidate set; the operator raises the
                # width (or turns the sparse path off)
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.FAILED_PRECONDITION, str(exc))
                raise
            except DeadlineExpired as exc:
                # already counted stage="gather" by the dispatcher hook
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(exc))
                raise
            return entry.reply
        finally:
            gate.__exit__(None, None, None)

    def _serve_brownout(self, req, tspan=None) -> Optional["pb2.ScoreReply"]:
        """Serve one breaker-open Score STALE from the brownout cache,
        or return None when the bound (or the cache's coverage) refuses
        it.  The reply carries ``degraded=True`` and certifies a
        generation at most ``--brownout-max-lag`` behind the id the
        client named — same epoch, same geometry, same CycleConfig, a
        top-k no wider than the cached launch.  Host numpy only: the
        whole point is answering without touching the failing device.
        A traced serve fan-in links ``tspan`` to the launch span that
        produced the cached bytes (ISSUE 14): the degraded reply's
        provenance is one link-hop away in the assembled tree."""
        with self._state_lock:
            # the id contract is unchanged: the client must name the
            # CURRENT snapshot (its Sync ack) — brownout changes which
            # GENERATION the scores certify, never which id is live
            if self._stale_snapshot(
                getattr(req, "snapshot_id", "")
            ) is not None:
                return None
            cache = self._brownout
            if cache is None or cache["epoch"] != self._epoch:
                return None
            lag = self._generation - cache["gen"]
            if lag < 0 or lag > self._brownout_max_lag:
                return None
            if cache["cfg"] != self.cfg:
                return None
            st = self.state
            if (
                st.node_alloc is None
                or st.pod_requests is None
                or st.node_alloc.shape[0] != cache["nodes"]
                or st.pod_requests.shape[0] != cache["pods"]
            ):
                return None  # geometry moved: the cached rows misalign
            k = min(int(req.top_k) or cache["N"], cache["N"])
            # snapshot ts/ti/kb UNDER the lock: the wide path below
            # decides on this consistent read, never on a re-read — a
            # concurrent widener bumping cache["kb"] mid-flight must
            # not make this thread skip the re-rank while still
            # holding the pre-widen narrow columns
            ts, ti, kb = cache["ts"], cache["ti"], cache["kb"]
            if k > kb and cache.get("scores") is None:
                # wider top-k than the cached launch computed and no
                # full [P, N] scores cached (past the cell gate): the
                # refusal stands — the cache cannot invent columns
                return None
        if k > kb:
            # ROADMAP 6(a): rank the cached full scores on host —
            # bit-identical to the launch that would have run.  The
            # inputs are immutable on the entry, so a concurrent
            # widener computes the identical result; memoization is
            # idempotent and only the SAME entry widens (a newer
            # launch's cache never inherits a stale ranking).
            from koordinator_tpu.solver.topk import masked_top_k_host

            ts, ti = masked_top_k_host(
                cache["scores"], cache["feasible"], k
            )
            with self._state_lock:
                if self._brownout is cache and k > cache["kb"]:
                    cache["ts"], cache["ti"], cache["kb"] = ts, ti, k
        reply = self._assemble_score_reply(
            req, k, ts, ti, cache["feasible"],
            cache["valid"], cache["P"], degraded=True,
            # sparse cache entries (ISSUE 16) carry the launch's ok
            # matrix instead of a dense feasible tensor; the wide
            # re-rank path above never runs for them (scores is None)
            ok_full=cache.get("ok"),
        )
        if tspan is not None:
            tspan.link_ref(cache.get("launch_span"))
            tspan.set_attr("brownout_lag", lag)
        with self._state_lock:
            self.degraded_replies += 1
            self.telemetry.metrics.count_degraded("score")
        return reply

    # -- coalesced Score execution: launch phase (leader thread, launch
    #    lock held) returning the readback closure the dispatcher runs
    #    OFF the lock — the pipeline seam --
    @launch_section
    def _score_launch_batch(self, batch: List[PendingRequest]):
        # capture a consistent view under the state lock, then leave it:
        # the launch must not serialize Syncs
        with self._state_lock:
            sid = self.snapshot_id()
            accepted = []
            for entry in batch:
                err = self._stale_snapshot(
                    getattr(entry.req, "snapshot_id", ""), sid
                )
                if err is not None:
                    entry.error = err
                else:
                    accepted.append(entry)
            if not accepted:
                return None
            # Score memo (ISSUE 7 satellite): an unchanged (snapshot
            # id, CycleConfig) whose memoized k-bucket covers every
            # caller serves sliced prefixes of the memoized readback —
            # no launch, and no lazy cold snapshot rebuild either
            memo = memo_ks = None
            if self._score_memo is not None:
                memo = self._score_memo.get(sid, self.cfg)
            if memo is not None:
                memo_ks = [
                    min(int(e.req.top_k) or memo["N"], memo["N"])
                    for e in accepted
                ]
                if max(memo_ks) > memo["kb"]:
                    memo = None  # needs a wider launch; it will replace
            incr = None
            # geometry AT LAUNCH for the brownout cache: serve-time
            # checks compare against it so a resize between this launch
            # and a breaker-open serve can never misalign cached rows
            mirror_rows = (
                self.state.node_alloc.shape[0]
                if self.state.node_alloc is not None else 0,
                self.state.pod_requests.shape[0]
                if self.state.pod_requests is not None else 0,
            )
            sparse = self.cfg.candidate_width > 0
            cres = None
            if memo is None:
                try:
                    snap = self.state.snapshot()
                except Exception as exc:
                    # a failed cold rebuild is a server-side cycle
                    # failure the serial path counted and flight-dumped;
                    # keep that (abort_cycle under the state lock, as
                    # Sync does)
                    self.telemetry.abort_cycle("score", exc)
                    raise
                if sparse:
                    # sparse candidate engine (ISSUE 16): the resident
                    # [P, C] candidate lists, if any, with the dirt the
                    # warm commits since their build accumulated.  Same
                    # wholesale CycleConfig invalidation as the score
                    # residency — the lists certify one feasibility
                    # program.
                    cres = self.state.candidate_residency()
                    if cres is not None and cres.cfg != self.cfg:
                        self.state.drop_candidate_residency()
                        cres = None
                elif self._score_incr:
                    # incremental engine (ISSUE 9): the resident score
                    # tensors, if any, with the dirt the warm commits
                    # since their launch accumulated.  A CycleConfig
                    # change invalidates them wholesale — the tensors
                    # certify a different scoring program.
                    incr = self.state.score_residency()
                    if incr is not None and incr.cfg != self.cfg:
                        self.state.drop_score_residency()
                        incr = None
        if memo is not None:
            # the prefix assembly is pure host work: hand it back as a
            # no-device closure so it runs OFF the launch lock (like a
            # readback) without taking an in-flight slot — a memo hit
            # must not stall the next real launch behind numpy slicing
            def _serve(accepted=accepted, ks=memo_ks, memo=memo, sid=sid):
                return self._score_serve_memo(accepted, ks, memo, sid)

            _serve.no_device = True
            return _serve
        # fan-in tracing (ISSUE 14): ONE launch span for the whole
        # coalesced batch, parented under the first traced rider's RPC
        # span; every traced rider LINKS to it instead of each minting
        # its own — the tree shows N RPCs converging on one device
        # launch.  The span ends in the readback closure (off the
        # launch lock) or aborts on either half's failure below.
        traced = [e.trace_span for e in accepted
                  if e.trace_span is not None]
        launch_span = None
        if traced:
            lead = traced[0]
            launch_span = self.telemetry.spans.start_trace_span(  # ends in the readback closure the dispatcher always runs off the launch lock; both failure paths abort it explicitly
                "score_launch", lead.trace_id, parent_id=lead.span_id,
                kind="internal",
                attrs={"batch": len(accepted), "snapshot_id": sid},
            )
            for t in traced:
                t.link_ref(launch_span.ref)
        launch_ref = None if launch_span is None else launch_span.ref
        if sparse:
            # sparse [P, C] engine (ISSUE 16): candidate build/refresh +
            # gathered scoring replaces the dense [P, N] ladder below —
            # same readback-closure contract, same memo/brownout/
            # telemetry seams
            return self._score_launch_sparse(
                accepted, snap, cres, sid, mirror_rows,
                launch_span, launch_ref,
            )
        try:
            # execution clock starts HERE: the cycle-latency histogram
            # keeps the serialized daemon's semantics (device dispatch +
            # readback + assembly, no queue wait — queue wait has its
            # own koord_scorer_coalesce_queue_delay_ms family)
            with profiled("score_dispatch"):
                t_exec = time.perf_counter()
                N = snap.nodes.capacity
                P = snap.pods.capacity
                ks = [
                    min(int(e.req.top_k) or N, N) for e in accepted
                ]
                # ONE launch serves every caller: top_k runs at the batch
                # max, padded to the sticky power-of-two bucket so varying
                # batch composition cannot mint new compiled shapes (zero
                # jit cache misses on the warm path); each caller's k is a
                # prefix of the padded result (lax.top_k sorts descending
                # with index tie-breaks, so prefixes are exact)
                k_launch = min(pad_bucket(max(ks)), N)
                # engine ordering (ISSUE 9): memo (handled above) ->
                # incremental column/row rescore of the resident tensors ->
                # full score_cycle.  All three hand bit-identical tensors
                # to the SAME masked top_k below.
                scores = feasible = None
                incr_result = None  # telemetry: incr | full | fallback
                incr_cols = 0
                if self._score_incr:
                    if incr is not None:
                        ratio = (
                            len(incr.dirty_nodes) / N + len(incr.dirty_pods) / P
                        )
                        if ratio <= self._score_incr_max_ratio:
                            incr_cols = len(incr.dirty_nodes)
                            # the launch consumes the donated residency:
                            # unpublish it first, so a failed launch (which
                            # fails this batch) never leaves a consumed
                            # buffer for the next Score; success re-stores
                            # the advanced tensors below
                            self.state.drop_score_residency()
                            scores, feasible = self._score_incremental(
                                snap, incr
                            )
                            incr_result = "incr"
                        else:
                            # mostly-dirty: a full rescore is cheaper than
                            # scattering most of the tensor incrementally
                            incr_result = "fallback"
                    else:
                        incr_result = "full"  # nothing resident to advance
                if scores is None:
                    scores, feasible = score_cycle(snap, self.cfg)
                if self._score_incr:
                    # the tensors this launch certifies become (or refresh)
                    # the residency; accumulated dirt clears with the store
                    self.state.store_score_result(self.cfg, scores, feasible)
                # masked top-k via the packed-f64 fast path (solver/topk.py):
                # bit-identical to lax.top_k on the masked i64 tensor, ~30x
                # cheaper on CPU — the shared tail both engines pay, so the
                # incremental saving is not buried under an integer sort
                top_scores, top_idx = masked_top_k(
                    scores, feasible, k=k_launch,
                    hi=score_upper_bound(self.cfg),
                )
                # brownout full cache (ROADMAP 6(a)): a defensive device
                # COPY under the cell gate — the stored residency tensor is
                # DONATED by a subsequent pipelined incremental launch (the
                # very reason feasible is never donated), so the buffer
                # this readback will device_get must be its own
                cache_full = P * N <= self._brownout_full_cells
                scores_cache = None
                if cache_full:
                    import jax.numpy as jnp

                    scores_cache = jnp.copy(scores)
                # launch phase ends with the program ENQUEUED (async
                # dispatch); everything below blocks, so it lives in the
                # readback closure the dispatcher runs off the launch lock
                dispatch_s = time.perf_counter() - t_exec
        except Exception as exc:
            if launch_span is not None:
                launch_span.abort(exc)
            with self._state_lock:
                self.telemetry.abort_cycle("score", exc)
            raise

        def _readback():
            try:
                t0 = time.perf_counter()
                # one stacked device->host transfer for the whole batch
                # (the serialized daemon paid one blocking readback per
                # request), overlapped with the NEXT batch's launch by
                # the pipelined dispatcher.  Small tensors also fetch
                # the full [P, N] scores (the launch-section copy) for
                # the brownout cache (ROADMAP 6(a)): a breaker-open
                # wider-k request is then ranked on host instead of
                # refused; past the cell gate the extra transfer is
                # skipped — the hot path must not pay O(P x N)
                # readback at headline scale.
                with profiled("score_readback"):
                    ts, ti, feasible_np, valid_np, scores_np = (
                        jax.device_get((top_scores, top_idx, feasible,
                                        snap.pods.valid, scores_cache))
                    )
                readback_s = time.perf_counter() - t0
                # device work is done: the launch span closes HERE (off
                # the launch lock), covering async dispatch + the
                # stacked transfer — per-entry assembly failures are
                # the individual RPC spans' errors, not the launch's
                if launch_span is not None:
                    launch_span.set_attr("k_bucket", k_launch)
                    launch_span.end()
                ti = ti.astype(np.int32)
                valid = valid_np[:P].astype(bool)
                # publish the padded readback for Score-storm reuse —
                # only while the snapshot it certified is still current
                # (the id is in the key, so even a racing publish could
                # never serve a future generation; the guard just keeps
                # the dict from carrying a dead entry until the next
                # bump's clear)
                with self._state_lock:
                    if (
                        self._score_memo is not None
                        and sid == self.snapshot_id()
                    ):
                        self._score_memo.put(sid, self.cfg, dict(
                            kb=k_launch, N=N, P=P, ts=ts, ti=ti,
                            feasible=feasible_np, valid=valid,
                            launch_span=launch_ref,
                        ))
                    # brownout cache (ISSUE 13): unlike the memo this
                    # SURVIVES generation bumps — bounded staleness is
                    # what the breaker-open path serves.  Pipelined
                    # readbacks may complete out of order, so an older
                    # launch never replaces a newer cache entry.
                    b_epoch, _, b_gen = sid[1:].rpartition("-")
                    try:
                        b_gen = int(b_gen)
                    except ValueError:
                        b_gen = -1
                    prev = self._brownout
                    # b_epoch must ALSO match the current epoch: a
                    # rebase keeps the generation, so an out-of-order
                    # pre-rebase readback could otherwise satisfy the
                    # gen comparison and clobber a fresh post-rebase
                    # entry with one the serve-time epoch check will
                    # forever refuse
                    if b_gen >= 0 and b_epoch == self._epoch and (
                        prev is None
                        or prev["epoch"] != self._epoch
                        or b_gen >= prev["gen"]
                    ):
                        self._brownout = dict(
                            epoch=b_epoch, gen=b_gen, cfg=self.cfg,
                            kb=k_launch, N=N, P=P,
                            nodes=mirror_rows[0], pods=mirror_rows[1],
                            ts=ts, ti=ti, feasible=feasible_np,
                            valid=valid, launch_span=launch_ref,
                            scores=scores_np,
                        )
                # host-side assembly failures are per-entry: the launch
                # served everyone else, so one bad demux must not fail
                # callers whose replies are already built — and routing
                # them per-entry is what keeps the dispatcher's lifetime
                # stats (which count error-free entries) agreeing with
                # the koord_scorer_coalesce_* counters the hook feeds
                assembled = []
                n_failed = 0
                for entry, k in zip(accepted, ks):
                    try:
                        entry.reply = self._assemble_score_reply(
                            entry.req, k, ts, ti, feasible_np, valid, P,
                        )
                        assembled.append(entry)
                    except Exception as exc:  # routed to the one caller as its RPC error; sibling replies stand
                        entry.error = exc
                        n_failed += 1
                exec_ms = (time.perf_counter() - t_exec) * 1000.0
            except Exception as exc:
                if launch_span is not None:
                    launch_span.abort(exc)
                with self._state_lock:
                    self.telemetry.abort_cycle("score", exc)
                raise
            # returned as the post-batch hook: the dispatcher runs it
            # after followers were notified, so telemetry never extends
            # the readback path either
            return lambda: self._score_telemetry(
                assembled, sid, dispatch_s, readback_s, exec_ms, n_failed,
                incr_result=incr_result, incr_cols=incr_cols,
            )

        return _readback

    @launch_section
    def _score_launch_sparse(self, accepted, snap, cres, sid, mirror_rows,
                             launch_span, launch_ref):
        """Sparse candidate-set Score launch (ISSUE 16): score [P, C]
        gathered cells instead of the dense [P, N] wall.  Caller is
        :meth:`_score_launch_batch` (launch lock held, riders already
        filtered, launch span already fanned in); returns the same
        readback-closure shape the dense path returns.

        Engine ladder: reuse the resident candidate lists when clean;
        lazily merge-refresh the entries the warm commits dirtied
        (reason "dirty"); force a full blocked rebuild past the
        staleness bound (reason "stale") or with nothing resident
        (reason "cold").  The gathered cells run the SAME cellwise
        term stack as the dense launch, so wherever every pod's
        feasible fan-out fits C the reply bytes are identical to
        dense; when some pod's exact feasible count exceeds C the
        readback raises :class:`CandidateOverflow` — the engine
        refuses rather than silently degrade to a truncated list."""
        try:
            with profiled("score_dispatch"):
                t_exec = time.perf_counter()
                N = snap.nodes.capacity
                P = snap.pods.capacity
                C = int(self.cfg.candidate_width)
                # a pod holds at most min(C, N) real candidates, so every
                # caller's k (and the memoized "N") clamps there — the
                # same derivation the dense path runs with N.  Both are
                # powers of two, so the k bucket stays within C and the
                # top-k shape never crosses a jit boundary traced.
                k_cap = min(C, N)
                ks = [min(int(e.req.top_k) or k_cap, k_cap) for e in accepted]
                k_launch = min(pad_bucket(max(ks)), k_cap)
                refresh_reason = None
                merges = 0
                if cres is None:
                    # cold: the pipelined build (ISSUE 20) engages past the
                    # block threshold — the node mesh, when configured,
                    # shards its counts pass over the block axis
                    cand, count = build_candidates(
                        snap, self.cfg, node_mesh=self.mesh
                    )
                    refresh_reason = "cold"
                elif cres.dirty_nodes or cres.dirty_pods:
                    if cres.merges >= self.cfg.candidate_max_stale:
                        # merge-chain bound hit: one full rebuild resets it
                        cand, count = build_candidates(
                            snap, self.cfg, node_mesh=self.mesh
                        )
                        refresh_reason = "stale"
                    else:
                        cand, count = refresh_candidates(
                            snap, cres.idx, cres.count,
                            sorted(cres.dirty_nodes), sorted(cres.dirty_pods),
                            self.cfg,
                        )
                        refresh_reason = "dirty"
                        merges = cres.merges + 1
                else:
                    cand, count = cres.idx, cres.count
                if refresh_reason is not None:
                    # the lists this launch certifies become the residency;
                    # accumulated dirt clears with the store
                    self.state.store_candidates(self.cfg, cand, count, merges)
                scores, feasible = score_candidates(snap, cand, self.cfg)
                top_scores, top_idx, top_ok = sparse_top_k(
                    scores, feasible, cand, k=k_launch,
                    hi=score_upper_bound(self.cfg),
                )
                dispatch_s = time.perf_counter() - t_exec
        except Exception as exc:
            if launch_span is not None:
                launch_span.abort(exc)
            with self._state_lock:
                self.telemetry.abort_cycle("score", exc)
            raise

        def _readback():
            try:
                t0 = time.perf_counter()
                # one stacked device->host transfer, like the dense
                # readback; the exact per-pod feasible counts ride
                # along for the overflow check
                with profiled("score_readback"):
                    ts, ti, ok_np, count_np, valid_np = jax.device_get(
                        (top_scores, top_idx, top_ok, count,
                         snap.pods.valid)
                    )
                readback_s = time.perf_counter() - t0
                try:
                    check_candidate_overflow(count_np, C)
                except CandidateOverflow:
                    # a truncating merge may have dropped real
                    # candidates: the lists must never refresh — drop
                    # them so the next sparse Score cold-rebuilds (and
                    # refuses again until the width is raised)
                    self.state.drop_candidate_residency()
                    raise
                if launch_span is not None:
                    launch_span.set_attr("k_bucket", k_launch)
                    launch_span.set_attr("candidate_width", C)
                    launch_span.end()
                ti = ti.astype(np.int32)
                ok_np = ok_np.astype(bool)
                valid = valid_np[:P].astype(bool)
                with self._state_lock:
                    if (
                        self._score_memo is not None
                        and sid == self.snapshot_id()
                    ):
                        # the precomputed ok matrix replaces the dense
                        # entries' [P, N] feasible tensor: the sparse
                        # feasibility is per-CELL, so take_along_axis
                        # against real node ids would misindex it
                        self._score_memo.put(sid, self.cfg, dict(
                            kb=k_launch, N=k_cap, P=P, ts=ts, ti=ti,
                            feasible=None, valid=valid, ok=ok_np,
                            launch_span=launch_ref,
                        ))
                    b_epoch, _, b_gen = sid[1:].rpartition("-")
                    try:
                        b_gen = int(b_gen)
                    except ValueError:
                        b_gen = -1
                    prev = self._brownout
                    if b_gen >= 0 and b_epoch == self._epoch and (
                        prev is None
                        or prev["epoch"] != self._epoch
                        or b_gen >= prev["gen"]
                    ):
                        # no full scores cached: a breaker-open
                        # wider-k request is refused (the cache cannot
                        # invent candidate columns this launch never
                        # scored); prefix serves within kb still work
                        self._brownout = dict(
                            epoch=b_epoch, gen=b_gen, cfg=self.cfg,
                            kb=k_launch, N=k_cap, P=P,
                            nodes=mirror_rows[0], pods=mirror_rows[1],
                            ts=ts, ti=ti, feasible=None, valid=valid,
                            ok=ok_np, launch_span=launch_ref,
                            scores=None,
                        )
                assembled = []
                n_failed = 0
                for entry, k in zip(accepted, ks):
                    try:
                        entry.reply = self._assemble_score_reply(
                            entry.req, k, ts, ti, None, valid, P,
                            ok_full=ok_np,
                        )
                        assembled.append(entry)
                    except Exception as exc:  # routed to the one caller as its RPC error; sibling replies stand
                        entry.error = exc
                        n_failed += 1
                exec_ms = (time.perf_counter() - t_exec) * 1000.0
            except Exception as exc:
                if launch_span is not None:
                    launch_span.abort(exc)
                with self._state_lock:
                    self.telemetry.abort_cycle("score", exc)
                raise
            return lambda: self._score_telemetry(
                assembled, sid, dispatch_s, readback_s, exec_ms, n_failed,
                cand_refresh=refresh_reason, cand_width=C,
            )

        return _readback

    @launch_section
    def _score_incremental(self, snap, res):
        """Advance the resident score tensors through the accumulated
        dirty columns/rows (solver/incremental.py ``rescore_dirty``) —
        the warm Score engine.  Caller holds the launch lock (commits
        that add dirt run under it too, so the sets cannot move); a
        failure raises to the batch's callers.

        The resident ``scores`` buffer is DONATED to the rescore: the
        caller unpublishes the residency before the launch, so a
        mid-kernel failure can never leave a consumed buffer published,
        and re-stores the advanced tensors on success.  With no dirt at
        all (a quota-only delta stream, or a wider-k relaunch over an
        unchanged snapshot) the tensors are already exact and pass
        through untouched — no kernel launches."""
        from koordinator_tpu.solver.incremental import rescore_dirty

        if not res.dirty_nodes and not res.dirty_pods:
            return res.scores, res.feasible
        scores, feasible = res.scores, res.feasible
        res.scores = res.feasible = None
        return rescore_dirty(
            snap, scores, feasible, res.dirty_nodes, res.dirty_pods,
            self.cfg, mesh=self.state.active_mesh(),
        )

    def _score_serve_memo(self, accepted, ks, memo, sid):
        """Serve a whole coalesced batch as sliced prefixes of the
        memoized padded top-k readback.  Host numpy only — no device
        launch, no snapshot capture — and bit-identical to a fresh
        launch (each caller's k is a prefix of the padded ``lax.top_k``
        the memo recorded, the same slice a live batch would take).
        Runs as the dispatcher's ``no_device`` closure: OFF the launch
        lock (assembly must not stall the next real launch) but with
        nothing entering the pipeline — no in-flight slot, no device
        idle charged.  Telemetry follows the Assign memo's contract:
        hits count on the score-memo family, feed the coalesce
        occupancy/queue-delay families, and observe the latency
        histogram under ``path="memo"`` — never ``path="score"``, so
        sub-millisecond prefix slices cannot skew the device-cycle
        percentiles.  A pending-free batch commits its own flight
        record (``path="memo"``, ``memo_hit`` note); with a pending
        Sync→Assign correlation open, only the counters move — a memo
        hit must not stamp the pending cycle."""
        t_exec = time.perf_counter()
        served = []
        n_failed = 0
        for entry, k in zip(accepted, ks):
            try:
                # traced memo hits fan-in link to the launch that
                # produced the cached readback (ISSUE 14): a prefix
                # slice's provenance is the ORIGINAL device launch,
                # possibly from another caller's trace
                if entry.trace_span is not None:
                    entry.trace_span.link_ref(memo.get("launch_span"))
                    entry.trace_span.set_attr("memo_hit", True)
                entry.reply = self._assemble_score_reply(
                    entry.req, k, memo["ts"], memo["ti"],
                    memo["feasible"], memo["valid"], memo["P"],
                    ok_full=memo.get("ok"),
                )
                served.append(entry)
            except Exception as exc:  # routed to the one caller as its RPC error; sibling replies stand
                entry.error = exc
                n_failed += 1
        exec_ms = (time.perf_counter() - t_exec) * 1000.0

        def _hook():
            # post-batch hook: sequenced under the state lock AFTER
            # followers were notified, exactly like _score_telemetry
            with self._state_lock:
                tel = self.telemetry
                for _ in range(n_failed):
                    tel.metrics.count_cycle_error("score")
                if not served:
                    return
                tel.metrics.count_score_memo("hit", len(served))
                tel.metrics.record_coalesce(
                    len(served), [e.queue_delay_ms for e in served]
                )
                pending = tel.spans.has_pending()
                n_observe = len(served) if pending else len(served) - 1
                if not pending:
                    tel.flush_backlog()
                    spans = tel.spans
                    # the record must say which snapshot the memoized
                    # readback certified — the correlation every other
                    # record type carries
                    spans.current(snapshot_id=sid)
                    if len(served) > 1:
                        spans.note("coalesced", len(served))
                    spans.note("memo_hit", True)
                    tel.commit_cycle(
                        exec_ms, path="memo", wave=self.cfg.wave
                    )
                for _ in range(n_observe):
                    tel.metrics.observe_cycle(
                        exec_ms, path="memo", wave=self.cfg.wave
                    )

        return _hook

    def _assemble_score_reply(
        self, req, k, top_scores, top_idx, feasible_np, valid, P,
        degraded: bool = False, ok_full=None,
    ) -> "pb2.ScoreReply":
        """Demux one caller's reply from the shared readback: slice the
        k-prefix of the padded top-k (bit-identical with a serial
        ``lax.top_k(masked, k)``), then the same flat/legacy assembly
        the serialized path used.  ``degraded`` stamps the brownout
        path's explicit staleness flag (ISSUE 13) — a fresh launch
        never sets it, so reply bytes off the breaker path are
        untouched.  ``ok_full``: the sparse engine (ISSUE 16) passes
        its precomputed [P, k_bucket] validity matrix instead of a
        dense [P, N] feasible tensor — sparse feasibility is per
        gathered CELL, so indexing it by real node id would misread
        it; the prefix slice keeps the bytes identical either way."""
        ts = top_scores[:, :k]
        ti = top_idx[:, :k]
        if ok_full is not None:
            ok = ok_full[:, :k]
        else:
            ok = np.take_along_axis(feasible_np, ti, axis=1)
        reply = pb2.ScoreReply()
        if degraded:
            reply.degraded = True
        t0 = time.perf_counter()
        if req.flat:
            # flat layout (round-3 review #8): O(1) Python calls —
            # boolean indexing + tobytes, no per-pod message building
            ok_v = ok[:P][valid]
            reply.flat.pod_index = (
                np.flatnonzero(valid).astype("<i4").tobytes()
            )
            reply.flat.counts = ok_v.sum(axis=1).astype("<i4").tobytes()
            reply.flat.node_index = (
                ti[:P][valid][ok_v].astype("<i4").tobytes()
            )
            reply.flat.score = (
                ts[:P][valid][ok_v].astype("<i8").tobytes()
            )
        else:
            # legacy per-pod lists: per-valid-pod Python loop
            for p in np.flatnonzero(valid):
                entry = reply.pods.add()
                m = ok[p]
                entry.node_index.extend(ti[p, m].tolist())
                entry.score.extend(ts[p, m].tolist())
        reply.build_ms = (time.perf_counter() - t0) * 1000.0
        return reply

    def _score_telemetry(self, assembled, sid, dispatch_s, readback_s,
                         exec_ms, n_failed=0, incr_result=None,
                         incr_cols=0, cand_refresh=None, cand_width=0):
        """Per-batch telemetry, sequenced under the state lock.  The
        pending-cycle contract is unchanged from the serial daemon: a
        pending cycle holds Sync stages awaiting the Assign that
        correlates them, so a Score must NOT commit it — its spans ride
        along (score_* names) and only a pending-free batch commits one
        record.  The cycle-latency histogram gets ONE observation per
        request, all at the batch's shared execution time (dispatch +
        readback + assembly — the same quantity the serialized daemon
        observed per request), so serial and coalesced streams count
        identically and queue wait stays in its own
        koord_scorer_coalesce_queue_delay_ms family.  Runs as the
        dispatcher's post-batch hook — after the device lock dropped.
        ``assembled`` holds only the entries whose replies were delivered
        (per-entry assembly failures were routed as those callers' RPC
        errors and arrive here as ``n_failed``), so every family below
        counts exactly what the dispatcher's lifetime stats count."""
        with self._state_lock:
            tel = self.telemetry
            for _ in range(n_failed):
                tel.metrics.count_cycle_error("score")
            if self._score_memo is not None and (assembled or n_failed):
                # every request in a LAUNCHED batch missed the memo
                tel.metrics.count_score_memo(
                    "miss", len(assembled) + n_failed
                )
            if incr_result is not None:
                # one observation per LAUNCH (the engines are per-batch
                # decisions, unlike the per-request memo counters)
                tel.metrics.count_score_incr(incr_result)
                if incr_result == "incr":
                    tel.metrics.observe_incr_cols(incr_cols)
            if cand_width:
                # sparse engine (ISSUE 16): the serving width gauge and
                # one refresh count per launch that rebuilt/re-merged
                # (a launch reusing clean lists counts nothing)
                tel.metrics.set_candidate_width(cand_width)
                if cand_refresh is not None:
                    tel.metrics.count_candidate_refresh(cand_refresh)
            if assembled or n_failed:
                # fused scoring terms (ISSUE 15): one count per DEVICE
                # launch per enabled term — the fused engine's "all
                # terms, one launch" claim made countable
                for term in self._term_names:
                    tel.metrics.count_term(term)
            if not assembled:
                return
            tel.flush_backlog()
            spans = tel.spans
            pending = spans.has_pending()
            spans.current(snapshot_id=sid)
            spans.add_measured("score_dispatch", dispatch_s)
            spans.add_measured("score_readback", readback_s)
            if len(assembled) > 1:
                spans.note("coalesced", len(assembled))
            tel.metrics.record_coalesce(
                len(assembled), [e.queue_delay_ms for e in assembled]
            )
            # pipeline health rides the same hook: the live adaptive
            # window and the cumulative device-idle wall time
            stats = self.dispatch.stats()
            tel.metrics.set_coalesce_window(stats["window_ms"])
            tel.metrics.set_device_idle(stats["device_idle_ms"])
            n_observe = len(assembled) if pending else len(assembled) - 1
            if not pending:
                tel.commit_cycle(exec_ms, path="score", wave=self.cfg.wave)
            for _ in range(n_observe):
                tel.metrics.observe_cycle(
                    exec_ms, path="score", wave=self.cfg.wave
                )

    def assign(self, req: "pb2.AssignRequest", ctx=None) -> "pb2.AssignReply":
        """Tracing shell over :meth:`_assign_impl` (see :meth:`sync`).
        The profiler annotations (obs/spans.py): koord.assign over the
        body, and koord.assign_queue from here to the device launch —
        closed by ``launch()``, by a memo serve, or here at the latest."""
        with profiled("assign") as ann:
            queue = profiled("assign_queue")
            self._rpc_local.assign_queue = queue.__enter__()
            try:
                reply = self._assign_traced(req, ctx)
            finally:
                self._end_assign_queue()
            tag_cycle(ann, reply)
        return reply

    def _end_assign_queue(self, cycle=None) -> None:
        """Close this thread's open koord.assign_queue annotation (once),
        stamping ``cycle`` (a CycleScope) when given."""
        ann = getattr(self._rpc_local, "assign_queue", None)
        if ann is not None:
            self._rpc_local.assign_queue = None
            if cycle is not None:
                tag_cycle(ann, cycle)
            ann.__exit__(None, None, None)

    def _assign_traced(self, req, ctx) -> "pb2.AssignReply":
        tspan = self._start_rpc_span(
            "assign", req, band=getattr(req, "band", "") or "",
        )
        if tspan is None:
            return self._assign_impl(req, ctx, None)
        try:
            reply = self._assign_impl(req, ctx, tspan)
        except BaseException as exc:
            tspan.abort(exc)
            raise
        tspan.set_attr("cycle_id", reply.cycle_id)
        reply.server_span = tspan.span_id
        tspan.end()
        return reply

    def _assign_impl(self, req: "pb2.AssignRequest", ctx=None,
                     tspan=None) -> "pb2.AssignReply":
        # same admission gate as Score (ISSUE 8): Assign is read
        # traffic against the resident snapshot, so it sheds with the
        # same RESOURCE_EXHAUSTED-before-the-queue-drowns contract —
        # band-aware since ISSUE 13 (free sheds first, prod last)
        band = getattr(req, "band", "") or ""
        try:
            gate = self.admission.admit("assign", band)
            gate.__enter__()
        except ResourceExhausted as exc:
            self.telemetry.metrics.count_shed("assign", band)
            if ctx is not None:
                ctx.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc))
            raise
        try:
            # deadline propagation (ISSUE 13): an already-exhausted
            # budget is refused before it can queue behind the device
            budget = self._deadline_budget_ms(req, ctx)
            if budget < 0.0:
                self.telemetry.metrics.count_deadline_expired("queue")
                exc = DeadlineExpired("assign", "queue", budget)
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(exc))
                raise exc
            # breaker (ISSUE 13): Assign must NOT serve stale — a
            # placement certifies the live snapshot — so an open
            # breaker fails fast with retry-after instead of queueing
            # this RPC behind a failing device
            if not self.breaker.allow_launch():
                self.telemetry.metrics.count_breaker_rejected("assign")
                exc = BreakerOpen(
                    "assign", self.breaker.retry_after_ms(),
                    "assign never serves stale",
                )
                if ctx is not None:
                    ctx.abort(grpc.StatusCode.UNAVAILABLE, str(exc))
                raise exc
            deadline_at = (
                time.perf_counter() + budget / 1000.0
                if budget > 0.0 else None
            )
            # bounded retry: a waiter that inherited an OWNER's failure
            # re-runs the memo protocol (the failed entry was removed,
            # so one waiter promotes to owner); the last attempt
            # bypasses the memo entirely and computes its own cycle, so
            # a pathologically failing owner can never starve waiters
            for attempt in range(3):
                outcome = self._assign_once(
                    req, ctx, bypass_memo=attempt == 2,
                    deadline_at=deadline_at, budget_ms=budget,
                    tspan=tspan,
                )
                if outcome is not None:
                    return outcome
            raise RuntimeError(
                "unreachable: memo-bypass attempt returned None"
            )
        finally:
            gate.__exit__(None, None, None)

    def _assign_once(
        self, req: "pb2.AssignRequest", ctx, bypass_memo: bool = False,
        deadline_at: Optional[float] = None, budget_ms: float = 0.0,
        tspan=None,
    ) -> Optional["pb2.AssignReply"]:
        """One pass of the Assign memo protocol.  Returns the reply, or
        None when this thread waited on a memo owner that failed (the
        caller retries).  ``bypass_memo`` computes a cycle without
        consulting or publishing the memo."""
        t_rpc = time.perf_counter()
        with self._state_lock:
            self._check_generation(req, ctx)
            sid = self.snapshot_id()
            key = (sid, self.cfg)
            owner = False
            entry = None
            if not bypass_memo:
                entry = self._assign_memo.get(key)
                if entry is None:
                    entry = _AssignMemo()
                    self._assign_memo[key] = entry
                    owner = True
            # per-RPC span scope (the ISSUE-6 correlation fix): the
            # cycle OWNER — the RPC whose device cycle will close the
            # Sync→Score→Assign flow — adopts the pending cycle
            # atomically; memo waiters mint fresh cycles and can no
            # longer relabel the open one or land stray stamps on it
            scope = self.telemetry.begin_rpc_scope(
                snapshot_id=sid,
                cycle_id=req.cycle_id or None,
                adopt_pending=owner or bypass_memo,
                trace_id=getattr(req, "trace_id", "") or None,
            )
            if tspan is not None and (owner or bypass_memo):
                # the owner's RPC span is what memo waiters link to:
                # publish the ref on the entry the waiters hold
                if entry is not None:
                    entry.span_ref = tspan.ref
        if entry is not None and not owner:
            if tspan is not None:
                # memo-served: fan-in link to the owner's span — the
                # device cycle this RPC's result actually came from
                tspan.link_ref(entry.span_ref)
                tspan.set_attr("memo_hit", True)
            # no device work will happen on this RPC: if assign()'s
            # allow_launch() granted it the one half-open probe slot,
            # that slot must free for a caller that WILL launch —
            # a memo hit says nothing about the device, and a wedged
            # probe slot would hold the breaker half-open forever
            # (release_probe is a no-op outside half-open)
            self.breaker.release_probe()
            return self._assign_from_memo(entry, scope, t_rpc)
        try:
            reply = self._assign_compute(
                req, ctx, scope, memo=entry,
                deadline_at=deadline_at, budget_ms=budget_ms,
                tspan=tspan,
            )
        except BaseException as exc:
            if owner:
                # unpublish BEFORE waiters act on it: the entry leaves
                # the dict so the next attempt mints a fresh owner
                with self._state_lock:
                    if self._assign_memo.get(key) is entry:
                        del self._assign_memo[key]
                    entry.error = exc
                    entry.done.set()
            raise
        return reply

    def _assign_from_memo(
        self, entry: _AssignMemo, scope, t_rpc: float
    ) -> Optional["pb2.AssignReply"]:
        """Serve one Assign from a published (or in-flight) memo entry.
        Waits OUTSIDE every lock; returns None (caller retries) when the
        owner failed — its error class may have been specific to that
        RPC, and serial semantics are re-established by re-running."""
        # backstop timeout (unbounded-wait idiom): the owner always
        # sets done — success AND failure paths — so the loop exits on
        # the event; the 1 Hz re-check only bounds a bug's blast radius
        while not entry.done.wait(timeout=1.0):
            pass
        if entry.error is not None or entry.result is None:
            # the waiter's private scope must not be abandoned: commit
            # it to the flight ring (no disk dump, no error counter —
            # the failed OWNER's abort already did both for the actual
            # cycle) so the record trail shows this RPC inherited the
            # owner's failure and retried
            exc = entry.error or RuntimeError(
                "memo owner published no result"
            )
            with self._state_lock:
                scope.note("memo_owner_failed", True)
                self.telemetry.abort_scope(
                    scope, "assign-memo-wait", exc, dump=False
                )
            return None
        assignment, status, valid, path, rounds, eff_wave, cycle_ms = (
            entry.result
        )
        wait_ms = (time.perf_counter() - t_rpc) * 1000.0
        self._end_assign_queue(scope)
        with profiled("assign_reply", scope), self._state_lock:
            reply = pb2.AssignReply(
                # the cycle that certified this assignment cost
                # ``cycle_ms`` on the device — that is what the field
                # has always meant; the memo wait itself is this RPC's
                # latency, carried by the "memo" histogram label
                cycle_ms=cycle_ms,
                path=path or "",
                cycle_id=scope.cycle_id,
            )
            reply.assignment.extend(assignment[valid].tolist())
            reply.status.extend(status[valid].tolist())
            self.telemetry.metrics.count_assign_memo("hit")
            scope.note("memo_hit", True)
            self.telemetry.commit_scope(
                scope, wait_ms, path="memo", wave=eff_wave, rounds=rounds
            )
        return reply

    def _assign_compute(
        self, req: "pb2.AssignRequest", ctx, scope,
        memo: Optional[_AssignMemo] = None,
        deadline_at: Optional[float] = None, budget_ms: float = 0.0,
        tspan=None,
    ) -> "pb2.AssignReply":
        """Run one real device cycle through the pipelined dispatcher
        and (as memo owner) publish its certified result.  ``memo`` is
        the owner's OWN entry object — published directly, never by
        dict re-lookup: a Sync's generation bump clears the dict
        mid-flight, and waiters already blocked on this entry must
        still be released (their result is serially consistent with
        the generation check they passed)."""
        # the cycle clock starts inside the launch section (below), so
        # cycle_ms and the latency histogram keep the serialized
        # daemon's meaning — device cycle + readback, NOT time spent
        # queued behind other launches (the coalesce families carry
        # queueing)
        t0 = [0.0]
        pod_gang = [None]

        @launch_section
        def launch():
            # capture INSIDE the launch section: a pipelined Sync's
            # delta scatter DONATES the pre-delta resident buffers, so
            # a snapshot captured before this RPC held the launch lock
            # could be deleted out from under the cycle (the stress
            # test in tests/test_coalesce.py reproduces exactly that).
            # The generation re-check is the pipeline seam's guard: if
            # a Sync committed while we queued, a pinned snapshot_id is
            # now stale and must FAILED_PRECONDITION, same as if the
            # RPCs had serialized Sync-first.  Once launched, the
            # in-flight slot keeps a donating Sync OUT (run_exclusive
            # drains) until the readback below completes.
            t0[0] = time.perf_counter()
            self._end_assign_queue(scope)
            # gather-stage deadline check (ISSUE 13): the budget may
            # have drained while this RPC waited for pipeline headroom
            # and the launch lock — an expired Assign must fail HERE,
            # before the full device cycle it can no longer use
            if deadline_at is not None and t0[0] >= deadline_at:
                raise DeadlineExpired("assign", "gather", budget_ms)
            # the dispatch span: snapshot capture and the launch
            with scope.span("dispatch"):
                with self._state_lock:
                    self._check_generation(req, None)
                    snap = self.state.snapshot()
                    i32_ok = self.state.i32_fits()
                    has_gangs = self.state.has_gangs()
                    # the gang ids the cycle ran with, for its counters
                    pod_gang[0] = self.state.pod_gang if has_gangs else None
                result, rounds, eff_wave = self._assign_cycle(
                    snap, scope, i32_ok, has_gangs
                )

            def _readback():
                # blocking stacked transfer — OFF the launch lock, so a
                # coalesced Score batch can launch while it drains.
                # ``rounds`` rides the same stacked device_get: a
                # device scalar (single-chip wave path, dense kernel),
                # a host int (shard path) or None (scan) — device_get
                # passes the last two through.
                with scope.span("readback"):
                    assignment, status, valid, got_rounds = jax.device_get(
                        (result.assignment, result.status,
                         snap.pods.valid, rounds)
                    )
                return (
                    result,
                    None if got_rounds is None else int(got_rounds),
                    eff_wave,
                    assignment, status, valid.astype(bool),
                )

            return _readback

        try:
            # the launch rides the pipelined dispatch queue: ordered
            # against coalesced Score launches and Sync's donating
            # scatters, with the readback off the launch critical
            # section so neither blocks behind the transfer
            result, rounds, eff_wave, assignment, status, valid = (
                self.dispatch.run_pipelined(launch)
            )
        except SnapshotNotResident as exc:
            # displaced mid-queue by another client's Sync: a client
            # protocol condition (the Go client full-resyncs on it),
            # not a cycle failure — no flight dump, no error counter,
            # but the RPC's OWN record says what happened instead of
            # its stamps landing on the pending cycle (the ISSUE-6
            # correlation fix)
            with self._state_lock:
                scope.note("displaced", True)
                self.telemetry.abort_scope(scope, "assign", exc, dump=False)
            if ctx is not None:
                ctx.abort(grpc.StatusCode.FAILED_PRECONDITION, str(exc))
            raise
        except DeadlineExpired as exc:
            # the CLIENT's budget ran out while this RPC queued: a
            # protocol condition like displacement, not a cycle failure
            # — no flight dump, no error counter, the record says what
            # happened (and the device never launched)
            with self._state_lock:
                self.telemetry.metrics.count_deadline_expired("gather")
                scope.note("deadline_expired", True)
                self.telemetry.abort_scope(scope, "assign", exc, dump=False)
            if ctx is not None:
                ctx.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(exc))
            raise
        except Exception as exc:
            # count + flight-dump the bad cycle before surfacing it
            with self._state_lock:
                self.telemetry.abort_scope(scope, "assign", exc)
            raise
        ms = (time.perf_counter() - t0[0]) * 1000.0
        with profiled("assign_reply", scope), self._state_lock:
            reply = pb2.AssignReply(
                cycle_ms=ms,
                path=result.path or "",
                cycle_id=scope.cycle_id,
            )
            reply.assignment.extend(assignment[valid].tolist())
            reply.status.extend(status[valid].tolist())
            self._count_gangs(status, pod_gang[0])
            # publish for concurrent waiters — on the OWNED entry: if a
            # Sync bumped the generation while the readback drained,
            # the dict slot is already gone (cleared under this very
            # lock) and stays gone, so future Assigns miss; waiters
            # blocked on this object still consume a result that is
            # serially consistent with the generation check they passed
            if memo is not None:
                memo.result = (
                    assignment, status, valid,
                    result.path or "", rounds, eff_wave, ms,
                )
                memo.done.set()
            self.telemetry.metrics.count_assign_memo("miss")
            self.telemetry.commit_scope(
                scope, ms,
                path=result.path or "unknown",
                wave=eff_wave,
                rounds=rounds,
            )
        return reply

    def _count_gangs(self, status, pod_gang) -> None:
        """The Strict gang counters of one Assign cycle, from the status
        vector read back: members placed then released are WAIT_GANG,
        and the gangs they belong to were rejected after placing."""
        if pod_gang is None:
            return
        released = status[: len(pod_gang)] == STATUS_WAIT_GANG
        if released.any():
            self.telemetry.metrics.count_gang_rejections(
                len(np.unique(pod_gang[released])), int(released.sum())
            )

    @launch_section
    def _assign_cycle(self, snap, spans, i32_ok, has_gangs=False):
        """Run the device cycle (shard-first when a mesh is configured)
        and return ``(CycleResult, rounds or None, effective wave
        width)`` — the shard path widens cfg.wave<=1 to its own
        default, and the telemetry labels must say what actually ran.
        Caller holds the launch lock and owns error accounting.

        ``spans`` is the RPC's CycleScope (obs/spans.py) — same span
        surface as the recorder, but private to this cycle."""
        # fused scoring terms (ISSUE 15): the multi-chip wave cycle has
        # no extras seam, so a term-enabled config serves Assign through
        # the single-chip run_cycle below (which folds the term tensors
        # into extra_mask/extra_scores) — bit-identical placements, the
        # reply's path field shows what ran.  Score keeps its full mesh
        # path either way: the terms live INSIDE score_all.
        # a snapshot with Strict gangs (``has_gangs``, from the mirrors)
        # serves through run_cycle too: only the dense kernel and the
        # scan roll a rejected gang back
        if self.mesh is not None and not self._term_names and not has_gangs:
            from koordinator_tpu.parallel import greedy_assign_waves

            # the CycleConfig wave knobs thread through to the
            # round-based sharded cycle; wave=1 (the per-pod default)
            # keeps the multichip path's own proven width.  A failed
            # shard cycle raises: the RPC fails, no single-chip
            # fallback hides it.
            eff_wave = self.cfg.wave if self.cfg.wave > 1 else 32
            with spans.span("dispatch_shard"):
                result, rounds = greedy_assign_waves(
                    snap, self.mesh, self.cfg,
                    wave=eff_wave, top_m=self.cfg.top_m, spans=spans,
                )
            return result, rounds, eff_wave
        result = run_cycle(snap, self.cfg, i32_ok=i32_ok, has_gangs=has_gangs)
        # device-derived step count (wave rounds, dense kernel pod
        # steps): returned UN-materialized (a device scalar) — blocking
        # on it here would hold the launch lock for the whole cycle; the
        # pipelined readback fetches it in the same stacked device_get
        # as assignment/status, off the lock
        return result, result.rounds, 1 if has_gangs else self.cfg.wave


def _handler(fn, req_cls, method: str, metrics):
    """One unary method; its deserializer counts the request's bytes
    on ``koord_scorer_rpc_request_bytes_total{method}``."""

    def deserialize(data: bytes):
        metrics.count_request_bytes(method, len(data))
        return req_cls.FromString(data)

    return grpc.unary_unary_rpc_method_handler(
        lambda req, ctx: fn(req, ctx),
        request_deserializer=deserialize,
        response_serializer=lambda msg: msg.SerializeToString(),
    )


def make_server(
    servicer: Optional[ScorerServicer] = None,
    cfg: CycleConfig = DEFAULT_CYCLE_CONFIG,
    max_workers: int = 16,
    mesh=None,
) -> grpc.Server:
    """``max_workers`` defaults to the reference scheduler's 16 parallel
    Score workers: with the coalescing dispatcher a full worker burst
    now shares one device launch instead of queueing on a lock, so the
    transport should not be the narrower funnel.  (Client-side, pass
    ``channels=N`` to ScorerClient so the burst actually arrives over
    parallel HTTP/2 connections — see bridge/client.py.)"""
    servicer = servicer or ScorerServicer(cfg, mesh=mesh)
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        # unbounded frames: a sparse-scale cluster's first full Sync
        # (ISSUE 16 — node counts past the dense allocator's reach)
        # ships hundreds of MB of node tensors in one request, far
        # past gRPC's 4 MB default receive cap
        options=(
            ("grpc.max_receive_message_length", -1),
            ("grpc.max_send_message_length", -1),
        ),
    )
    metrics = servicer.telemetry.metrics
    handlers = {
        "Sync": _handler(servicer.sync, pb2.SyncRequest, "sync", metrics),
        "Score": _handler(servicer.score, pb2.ScoreRequest, "score", metrics),
        "Assign": _handler(
            servicer.assign, pb2.AssignRequest, "assign", metrics
        ),
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),)
    )
    server._koord_servicer = servicer  # test/introspection seam
    return server


def serve_uds(
    path: str, cfg: CycleConfig = DEFAULT_CYCLE_CONFIG, mesh=None
) -> grpc.Server:
    """Bind the scorer on a unix-domain socket (the reference's CRI proxy
    transport, criserver.go:93) and start it.  Pass a multi-device
    ``mesh`` to serve the round-based sharded cycle (path="shard")."""
    server = make_server(cfg=cfg, mesh=mesh)
    server.add_insecure_port(f"unix://{path}")
    server.start()
    return server
