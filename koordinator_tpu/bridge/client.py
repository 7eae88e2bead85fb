"""Client shim for the BatchedScorer sidecar.

Plays the role the reference's in-scheduler plugin boundary plays
(Score/ScoreExtensions at ``frameworkext/framework_extender.go:216``): a
host scheduler embeds this client, syncs its cluster view (full once,
sparse deltas on warm cycles) and gets NodeScoreLists / assignments back.
"""

from __future__ import annotations

import itertools
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import grpc

from koordinator_tpu.bridge.codegen import method_path, pb2
from koordinator_tpu.bridge.state import numpy_to_tensor
from koordinator_tpu.obs.export import SpanExporter, resolve_export_dir
from koordinator_tpu.obs.lockwitness import witness_lock, witness_rlock
from koordinator_tpu.obs.spans import ClientTraceOp, profiled
from koordinator_tpu.replication.retry import BackoffPolicy

# channel-level failures: the RPC may or may not have reached the
# server, but the CLIENT state is intact — retryable through the shared
# backoff policy, and NEVER a reason to null the delta baseline (the
# generation-continuity check catches an ambiguous apply on the next
# acked Sync; ISSUE 11)
_TRANSIENT_CODES = (
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
)

# every stub call carries a transport deadline so a hung daemon can
# never hang the caller forever (the koordlint unbounded-wait rule's
# client half); generous by default — cold compiles are minutes on a
# slow host — and tightened per-call by the propagated deadline budget
DEFAULT_RPC_TIMEOUT_MS = 300_000.0

_RETRY_AFTER_RE = re.compile(r"retry_after_ms=(\d+(?:\.\d+)?)")


def _is_transient(exc: BaseException) -> bool:
    return (
        isinstance(exc, grpc.RpcError)
        and exc.code() in _TRANSIENT_CODES
    )


def _is_shed(exc: BaseException) -> bool:
    """An admission-gate shed (RESOURCE_EXHAUSTED + retry-after hint):
    transient BY CONTRACT — the server is healthy and said when to come
    back — and never a reason to touch the delta baseline."""
    return (
        isinstance(exc, grpc.RpcError)
        and exc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    )


def retry_after_ms(exc: BaseException) -> Optional[float]:
    """The machine-parsable ``retry_after_ms=<n>`` hint a shed or
    breaker-open reply carries, or None."""
    if not isinstance(exc, grpc.RpcError):
        return None
    m = _RETRY_AFTER_RE.search(exc.details() or "")
    return float(m.group(1)) if m else None


def _is_not_leader(exc: BaseException) -> bool:
    """The follower's Sync refusal (replication/follower.py): a probe
    result, not an error — the promoted leader is elsewhere."""
    return (
        isinstance(exc, grpc.RpcError)
        and exc.code() == grpc.StatusCode.FAILED_PRECONDITION
        and "one writer" in (exc.details() or "")
    )


def parse_snapshot_id(snapshot_id: str) -> Tuple[str, int]:
    """Server snapshot ids are "s<epoch>-<generation>" (bridge/server.py;
    the epoch is a per-boot nonce).  Legacy epoch-less "s<generation>" ids
    parse with an empty epoch; malformed ids yield generation -1, which
    never satisfies a continuity check."""
    body = snapshot_id[1:] if snapshot_id.startswith("s") else snapshot_id
    epoch, sep, gen = body.rpartition("-")
    if not sep:
        epoch, gen = "", body
    try:
        return epoch, int(gen)
    except ValueError:
        return epoch, -1


def parse_follower_target(target: str) -> Tuple[str, int]:
    """Split a follower target's optional relay-tree depth annotation
    (ISSUE 18): ``"unix:///f.sock@2"`` -> ``("unix:///f.sock", 2)``.
    Un-annotated targets are depth 1 (a direct follower — the flat-tier
    shape), and a trailing ``@<non-int>`` is treated as part of the
    address, not an annotation (abstract sockets and IPv6 hosts may
    legitimately contain ``@``)."""
    addr, sep, depth = target.rpartition("@")
    if sep:
        try:
            return addr, max(1, int(depth))
        except ValueError:
            pass
    return target, 1


class _ChannelPool:
    """Round-robin pool of independent gRPC channels (ISSUE 6).

    One grpc-python channel multiplexes every in-flight RPC onto ONE
    HTTP/2 connection, so a 16–64-way Score worker burst serializes on
    a single socket's flow control and wire ordering long before it
    reaches the coalescer — the raw-UDS shims (one socket per worker)
    never had this funnel.  Worse, gRPC core keeps a GLOBAL subchannel
    pool: two channels to the same target with identical channel args
    silently share one TCP/UDS connection, so naively creating N
    channels buys nothing.  Each pool slot therefore carries a distinct
    ``koord.pool_slot`` channel arg — distinct args key distinct
    subchannels, giving the burst ``size`` real parallel connections.
    Callers round-robin over ``channels`` themselves
    (``ScorerClient._slot`` builds one stub per channel up front and
    picks per call): cheap, and per-RPC affinity does not matter for
    unary calls."""

    def __init__(self, target: str, size: int):
        self.channels = [
            grpc.insecure_channel(
                target,
                # unbounded frames to match make_server: a sparse-scale
                # full Sync (ISSUE 16) is far past the 4 MB default
                options=(
                    ("koord.pool_slot", i),
                    ("grpc.max_receive_message_length", -1),
                    ("grpc.max_send_message_length", -1),
                ),
            )
            for i in range(max(1, int(size)))
        ]

    def close(self) -> None:
        for ch in self.channels:
            ch.close()


class ScorerClient:
    def __init__(self, target: str, channels: int = 1,
                 followers: Sequence[str] = (),
                 retry_policy: Optional[BackoffPolicy] = None,
                 band: str = "",
                 deadline_ms: Optional[float] = None,
                 rpc_timeout_ms: Optional[float] = None,
                 trace_export: Optional[str] = None):
        """``target``: "unix:///path.sock" or host:port.

        ``channels``: size of the connection pool Score/Assign calls
        round-robin over (default 1 keeps the single-channel behavior).
        Size it to the caller's worker parallelism (the reference
        scheduler runs 16 Score workers) so a burst reaches the
        coalescer concurrently instead of serializing on one HTTP/2
        connection.  Sync stays PINNED to the first channel: delta
        frames are order-sensitive against the acked baseline, and one
        connection preserves their wire order for free.

        ``followers`` (ISSUE 8, the replicated serving tier): targets
        of read-replica daemons.  Sync keeps going to ``target`` (the
        LEADER — the tier's one writer), Score round-robins over the
        followers, and a follower still catching up (its
        FAILED_PRECONDITION means "that generation has not replicated
        here yet", not "your baseline is wrong") falls back to the
        leader for that one call — replication lag degrades to leader
        reads, never to a failed cycle or a spurious full re-sync.
        Assign stays on the leader, whose snapshot is never behind.

        Tree-aware discovery (ISSUE 18, the relay tree): a follower
        target may carry a depth annotation — ``"unix:///f.sock@2"``
        means hop 2, i.e. behind one relay.  Score then round-robins
        over the DEEPEST layer only (the leaves): interior relays
        spend their bandwidth fanning the stream out to children, and
        the leaf layer is where aggregate read capacity multiplies.
        Un-annotated targets default to depth 1, so a flat follower
        list behaves exactly as before; writer failover probes still
        visit every follower regardless of depth (a promotion can land
        anywhere in the tree).

        ``retry_policy`` (ISSUE 11): the shared jittered-exponential
        backoff/deadline budget (``replication.retry.BackoffPolicy``;
        default from the ``KOORD_RETRY_*`` envs) that paces every
        channel-level retry.  Transient UDS/channel errors
        (``UNAVAILABLE``/``DEADLINE_EXCEEDED``) retry WITHOUT touching
        the delta baseline — the generation-continuity check on the
        next acked reply is what guards an ambiguous apply, so a
        replayed delta can never silently double-apply — and when
        ``followers`` are configured the Sync/Assign retries PROBE
        them for a promoted leader (a follower's "one writer" refusal
        means "not me, keep looking"), so a SIGUSR2/admin-RPC
        promotion fails over without reconfiguring the client.

        ``band`` (ISSUE 13): this client's priority band
        (koord-prod|mid|batch|free; empty = legacy, prod treatment),
        stamped on every Score/Assign so the daemon's admission gate
        sheds on the band ladder — free absorbs overload first, prod
        last.

        ``deadline_ms`` (ISSUE 13 deadline propagation): per-RPC
        deadline budget stamped onto the wire (``deadline_ms`` request
        field) AND set as the gRPC transport deadline for Score/Assign;
        the server evicts a request whose budget ran out before it
        occupies a launch slot.  Default from ``KOORD_DEADLINE_MS``
        (unset/empty = no propagated deadline).  Shed replies
        (RESOURCE_EXHAUSTED) and breaker fast-fails (UNAVAILABLE) carry
        a ``retry_after_ms`` hint; retries sleep the HINT in place of
        the backoff delay — one pause per attempt, never both, so the
        hint cannot double-count against the retry budget.

        ``rpc_timeout_ms``: transport deadline applied to EVERY stub
        call (``KOORD_RPC_TIMEOUT_MS``, default 300 s) so a hung daemon
        can never hang the caller forever; ``deadline_ms`` tightens it
        per call when set.

        ``trace_export`` (ISSUE 14, distributed tracing): directory
        this client appends its OWN completed spans to as OTLP-shaped
        JSON lines (default from ``KOORD_TRACE_EXPORT``; None/unset =
        tracing off, zero cost).  When on, every logical RPC mints ONE
        trace id and a root op span, every ATTEMPT — retries, failover
        probes, the Sync full-resend — gets a child span whose id is
        stamped as the wire ``parent_span``, and the server's echoed
        ``server_span`` is recorded on the attempt.  A retried-then-
        shed-then-served request therefore assembles into one tree
        with one span per attempt (``python -m
        koordinator_tpu.obs.assemble`` over the export dirs)."""
        self._pool = _ChannelPool(target, channels)
        self.band = band or ""
        # `or`: empty env value means unset (the KOORD_* convention)
        if deadline_ms is None:
            env = os.environ.get("KOORD_DEADLINE_MS") or ""
            deadline_ms = float(env) if env else 0.0
        self._deadline_ms = max(0.0, float(deadline_ms))
        if rpc_timeout_ms is None:
            rpc_timeout_ms = float(
                os.environ.get("KOORD_RPC_TIMEOUT_MS")
                or DEFAULT_RPC_TIMEOUT_MS
            )
        self._rpc_timeout_ms = max(1.0, float(rpc_timeout_ms))
        self._channel = self._pool.channels[0]  # Sync's pinned channel
        self._retry = retry_policy or BackoffPolicy.from_env()

        def unary(channel, method, reply_cls):
            return channel.unary_unary(
                method_path(method),
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=reply_cls.FromString,
            )

        self._sync = unary(self._channel, "Sync", pb2.SyncReply)
        self._scores = [
            unary(ch, "Score", pb2.ScoreReply) for ch in self._pool.channels
        ]
        self._assigns = [
            unary(ch, "Assign", pb2.AssignReply)
            for ch in self._pool.channels
        ]
        parsed = [parse_follower_target(t) for t in followers]
        self._follower_depths = [d for _, d in parsed]
        self._follower_pools = [
            _ChannelPool(t, 1) for t, _ in parsed
        ]
        # the leaf layer: indices at the tree's maximum depth — the
        # Score round-robin set (see the docstring's tree-aware
        # discovery contract); every index stays in the writer probe
        # order
        max_depth = max(self._follower_depths, default=0)
        self._leaf_indices = [
            i for i, d in enumerate(self._follower_depths)
            if d == max_depth
        ]
        self._follower_scores = [
            unary(p.channels[0], "Score", pb2.ScoreReply)
            for p in self._follower_pools
        ]
        self._follower_syncs = [
            unary(p.channels[0], "Sync", pb2.SyncReply)
            for p in self._follower_pools
        ]
        self._follower_assigns = [
            unary(p.channels[0], "Assign", pb2.AssignReply)
            for p in self._follower_pools
        ]
        # which target currently holds the writer role: -1 = the
        # configured leader; 0..N-1 = follower i, promoted (discovered
        # by the Sync probe's failover).  Writes move with it; Score
        # keeps its follower round-robin either way.
        self._leader_idx = -1
        self._rr = itertools.count()
        self._rr_lock = witness_lock("bridge.client.ScorerClient._rr_lock")
        # previous-ACKED-sync mirrors (tensor + scalar columns) for delta
        # encoding and full re-sync.  New values are staged per request and
        # promoted only after the server confirms the Sync, so a failed RPC
        # can never desync the delta baseline.  _baseline_lock makes the
        # whole sync() read-encode-promote sequence atomic against
        # _invalidate() running on a pooled worker thread (a concurrent
        # Score's FAILED_PRECONDITION): an unlocked clear mid-sync would
        # both corrupt the delta encode and null _generation, silently
        # disabling the displaced-baseline continuity check.
        self._baseline_lock = witness_rlock(
            "bridge.client.ScorerClient._baseline_lock")
        self._prev: Dict[str, np.ndarray] = {}
        self._prev_scalars: Dict[str, tuple] = {}
        self._generation: Optional[int] = None
        self._epoch: Optional[str] = None
        self.snapshot_id: Optional[str] = None
        # whether the last flat Score reply carried the brownout
        # degraded flag (ISSUE 13)
        self.last_degraded = False
        # distributed tracing (ISSUE 14): the client's own span
        # exporter; None = tracing off (the default)
        self._exporter: Optional[SpanExporter] = None
        export_to = resolve_export_dir(trace_export)
        if export_to is not None:
            self._exporter = SpanExporter(
                export_to, service="scorer-client"
            )

    def close(self) -> None:
        self._pool.close()
        for p in self._follower_pools:
            p.close()
        if self._exporter is not None:
            self._exporter.close()

    # -- distributed tracing (ISSUE 14) --
    def _trace_op(self, name: str) -> Optional[ClientTraceOp]:
        """One logical RPC's trace (root op span + per-attempt child
        spans), or None when tracing is off."""
        if self._exporter is None:
            return None
        return ClientTraceOp(name, sink=self._exporter.export)

    def _traced_call(self, op: Optional[ClientTraceOp], stub, request,
                     timeout: float):
        """One ATTEMPT: stamp the op's trace context on the request
        (each attempt re-stamps its own span id as ``parent_span``),
        invoke, record the server's echoed span id, end — or abort
        with the error so sheds/deadline/transport failures stay
        visible per attempt in the assembled tree."""
        if op is None:
            return stub(request, timeout=timeout)
        span = op.attempt()
        request.trace_id = op.trace_id
        request.parent_span = span.span_id
        try:
            reply = stub(request, timeout=timeout)
        except BaseException as exc:
            span.abort(exc)
            raise
        server_span = getattr(reply, "server_span", "") or ""
        if server_span:
            span.set_attr("server_span", server_span)
        span.end()
        return reply

    def _slot(self) -> int:
        with self._rr_lock:
            return next(self._rr) % len(self._scores)

    # -- writer routing + failover (ISSUE 11) --
    def _writer_stubs(self, kind: str):
        """``(idx, stub)`` probe order for a write-side RPC: the target
        last seen holding the writer role first, then every other
        candidate (the configured leader, then each follower) — a
        probe pass visits the whole tier once."""
        leader_stub = (
            self._sync if kind == "sync"
            else self._assigns[self._slot()]
        )
        table = [(-1, leader_stub)] + list(enumerate(
            self._follower_syncs if kind == "sync"
            else self._follower_assigns
        ))
        active = self._leader_idx
        table.sort(key=lambda e: 0 if e[0] == active else 1)
        return table

    def _timeout_s(self) -> float:
        """Transport deadline for one stub call: the client-wide cap,
        tightened by the propagated per-RPC budget when one is set."""
        t = self._rpc_timeout_ms
        if self._deadline_ms > 0.0:
            t = min(t, self._deadline_ms)
        return t / 1000.0

    def _pause_ms(self, delays, last: Optional[BaseException]):
        """The ONE pause before the next retry attempt, or None when
        the budget is spent: a server retry-after hint (shed/breaker
        replies) REPLACES the backoff delay for this attempt — the
        attempt still consumes its slot in the policy's deadline
        budget, so a hint can never double-count against it."""
        d_ms = next(delays, None)
        if d_ms is None:
            return None
        hint = retry_after_ms(last) if last is not None else None
        if hint is not None:
            # cap at the policy's backoff ceiling: a 30 s free-band
            # hint must not park a caller past its own retry budget
            return min(hint, self._retry.cap_ms)
        return d_ms

    def _call_writer(self, kind: str, request, op=None):
        """Invoke a writer-side RPC (Sync/Assign) against the active
        leader, failing over through the shared backoff policy:
        transient channel errors retry, "one writer" refusals probe
        the next candidate, admission sheds retry after the server's
        hint, anything else surfaces immediately (it is the SERVER's
        answer, and the caller's protocol logic — e.g. sync()'s
        full-resend fallback — owns it).  The delta baseline is never
        touched here: an ambiguous apply is caught by the continuity
        check on the next acked reply."""
        delays = self._retry.delays()
        timeout = self._timeout_s()
        while True:
            last: Optional[BaseException] = None
            for idx, stub in self._writer_stubs(kind):
                try:
                    reply = self._traced_call(op, stub, request, timeout)
                    self._leader_idx = idx
                    return reply
                except grpc.RpcError as exc:
                    if (
                        _is_not_leader(exc) or _is_transient(exc)
                        or _is_shed(exc)
                    ):
                        last = exc
                        continue
                    raise
            pause = self._pause_ms(delays, last)
            if pause is None:
                raise last
            time.sleep(pause / 1000.0)

    def _score_stub(self):
        """Score's routing: round-robin over the LEAF-layer follower
        replicas when configured (the deepest annotated depth — with a
        flat follower list that is every follower), else over the
        leader's own channel pool.  Returns ``(stub, is_follower)``."""
        if self._follower_scores:
            with self._rr_lock:
                i = self._leaf_indices[
                    next(self._rr) % len(self._leaf_indices)
                ]
            return self._follower_scores[i], True
        return self._scores[self._slot()], False

    def _leader_score_stub(self):
        """The active writer's Score stub — the lag-fallback target
        (after a promotion the configured leader may be DEAD; the
        fallback must follow the role, not the config)."""
        if 0 <= self._leader_idx < len(self._follower_scores):
            return self._follower_scores[self._leader_idx]
        return self._scores[self._slot()]

    def _call_score(self, request, op=None):
        """Reads retry FREELY (ISSUE 11): they are idempotent against a
        named snapshot, so a transient channel error just moves to the
        next replica under the shared backoff budget.  A shed
        (RESOURCE_EXHAUSTED) retries too, paced by the server's
        retry-after hint in place of the backoff delay (ISSUE 13)."""
        delays = self._retry.delays()
        timeout = self._timeout_s()
        while True:
            stub, on_follower = self._score_stub()
            if on_follower:
                try:
                    return self._traced_call(op, stub, request, timeout)
                except grpc.RpcError as e:
                    if _is_transient(e) or _is_shed(e):
                        pause = self._pause_ms(delays, e)
                        if pause is None:
                            raise
                        time.sleep(pause / 1000.0)
                        continue  # next replica round-robin
                    if e.code() != grpc.StatusCode.FAILED_PRECONDITION:
                        raise
                    # the follower has not applied this generation yet
                    # (replication lag) — the LEADER certified the id,
                    # so the baseline is fine: serve this call there
                    # instead of invalidating anything
            try:
                return self._call(self._leader_score_stub(), request, op=op)
            except grpc.RpcError as e:
                if not (_is_transient(e) or _is_shed(e)):
                    raise
                pause = self._pause_ms(delays, e)
                if pause is None:
                    raise
                time.sleep(pause / 1000.0)

    def _invalidate(self) -> None:
        with self._baseline_lock:
            self._prev.clear()
            self._prev_scalars.clear()
            self._generation = None
            self._epoch = None
            self.snapshot_id = None

    def _with_op(self, name: str, fn):
        """Run one logical RPC under a client trace op (ISSUE 14):
        ``fn(op)`` gets the op (or None with tracing off) to thread
        into the retrying call helpers; the root span ends — with the
        escaping error attached, or clean — on every exit."""
        op = self._trace_op(name)
        if op is None:
            return fn(None)
        try:
            result = fn(op)
        except BaseException as exc:
            op.finish(error=exc)
            raise
        op.finish()
        return result

    def sync(self, **kwargs) -> "pb2.SyncReply":
        """One logical Sync (delta-encoded against the acked baseline;
        see :meth:`_sync_op` for the keyword surface).  Traced as ONE
        op: the delta attempt, any failover probes and the full-resend
        fallback are sibling attempt spans of the same trace.  On the
        profiler's clock the call is ``koord.client.sync`` and each
        request build ``koord.client.sync_encode`` (obs/spans.py)."""
        with profiled("client.sync"):
            return self._with_op(
                "sync", lambda op: self._sync_op(op, **kwargs)
            )

    def _sync_op(
        self,
        op=None,
        *,
        node_allocatable: Optional[np.ndarray] = None,
        node_requested: Optional[np.ndarray] = None,
        node_usage: Optional[np.ndarray] = None,
        node_names: Sequence[str] = (),
        metric_fresh: Optional[Sequence[bool]] = None,
        pod_requests: Optional[np.ndarray] = None,
        pod_estimated: Optional[np.ndarray] = None,
        pod_names: Sequence[str] = (),
        priority: Optional[Sequence[int]] = None,
        gang_id: Optional[Sequence[int]] = None,
        quota_id: Optional[Sequence[int]] = None,
        gang_min_member: Sequence[int] = (),
        quota_runtime: Optional[np.ndarray] = None,
        quota_used: Optional[np.ndarray] = None,
        quota_limited: Optional[np.ndarray] = None,
        node_bucket: int = 0,
        pod_bucket: int = 0,
        node_accel_type: Optional[Sequence[int]] = None,
        workload_class: Optional[Sequence[int]] = None,
        pod_sensitivity: Optional[np.ndarray] = None,
        throughput: Optional[np.ndarray] = None,
    ) -> "pb2.SyncReply":
        tensors = {
            "nalloc": node_allocatable,
            "nreq": node_requested,
            "nuse": node_usage,
            "preq": pod_requests,
            "pest": pod_estimated,
            "qrt": quota_runtime,
            "quse": quota_used,
            "qlim": quota_limited,
            # fused-term tensors (ISSUE 15): the Synergy sensitivity
            # profile and the Gavel throughput matrix ride the same
            # delta-encoding path as every snapshot tensor
            "psens": pod_sensitivity,
            "tput": throughput,
        }
        scalars = {
            "node_names": tuple(node_names),
            "metric_fresh": (
                tuple(bool(b) for b in metric_fresh)
                if metric_fresh is not None
                else None
            ),
            "pod_names": tuple(pod_names),
            "priority": tuple(priority) if priority is not None else None,
            "gang_id": tuple(gang_id) if gang_id is not None else None,
            "quota_id": tuple(quota_id) if quota_id is not None else None,
            "gang_min": tuple(gang_min_member),
            "accel_type": (
                tuple(int(v) for v in node_accel_type)
                if node_accel_type is not None
                else None
            ),
            "workload_class": (
                tuple(int(v) for v in workload_class)
                if workload_class is not None
                else None
            ),
        }

        staged: Dict[str, np.ndarray] = {}
        staged_scalars: Dict[str, tuple] = {}

        def build(baseline: Dict[str, np.ndarray], full: bool):
            with profiled("client.sync_encode"):
                return _build(baseline, full)

        def _build(baseline: Dict[str, np.ndarray], full: bool):
            staged.clear()
            staged_scalars.clear()

            def tensor(key):
                arr = tensors[key]
                if full and arr is None:
                    arr = baseline.get(key)  # resend last acked state
                if arr is None:
                    return pb2.Tensor()
                a = np.ascontiguousarray(arr, np.int64)
                t = numpy_to_tensor(a, None if full else baseline.get(key))
                staged[key] = a
                return t

            def scalar(key):
                val = scalars[key]
                if (val is None or val == ()) and full:
                    val = self._prev_scalars.get(key)
                # the server treats empty repeated fields as "unchanged",
                # so only non-empty values become the acked baseline
                if val:
                    staged_scalars[key] = val
                return val

            req = pb2.SyncRequest(node_bucket=node_bucket, pod_bucket=pod_bucket)
            req.nodes.allocatable.CopyFrom(tensor("nalloc"))
            req.nodes.requested.CopyFrom(tensor("nreq"))
            req.nodes.usage.CopyFrom(tensor("nuse"))
            req.nodes.names.extend(scalar("node_names") or ())
            fresh = scalar("metric_fresh")
            if fresh is not None:
                req.nodes.metric_fresh.extend(fresh)
            req.pods.requests.CopyFrom(tensor("preq"))
            req.pods.estimated.CopyFrom(tensor("pest"))
            req.pods.names.extend(scalar("pod_names") or ())
            prio = scalar("priority")
            if prio is not None:
                req.pods.priority.extend(int(v) for v in prio)
            gang = scalar("gang_id")
            if gang is not None:
                req.pods.gang_id.extend(int(v) for v in gang)
            quota = scalar("quota_id")
            if quota is not None:
                req.pods.quota_id.extend(int(v) for v in quota)
            req.gangs.min_member.extend(int(v) for v in scalar("gang_min") or ())
            req.quotas.runtime.CopyFrom(tensor("qrt"))
            req.quotas.used.CopyFrom(tensor("quse"))
            req.quotas.limited.CopyFrom(tensor("qlim"))
            accel = scalar("accel_type")
            if accel is not None:
                req.nodes.accel_type.extend(accel)
            wclass = scalar("workload_class")
            if wclass is not None:
                req.pods.workload_class.extend(wclass)
            req.pods.sensitivity.CopyFrom(tensor("psens"))
            req.terms.throughput.CopyFrom(tensor("tput"))
            return req

        # the lock is held across the RPCs: a pooled Score thread's
        # _invalidate (FAILED_PRECONDITION on displacement) must not
        # clear the dict build() is delta-encoding from, nor null
        # _generation between the reply and the continuity check below
        # — it waits, then wipes the fresh baseline, and the NEXT sync
        # ships full state (a re-encode, never silent corruption)
        with self._baseline_lock:
            baseline = self._prev
            sent_full = False
            try:
                reply = self._call_writer(
                    "sync", build(baseline, full=False), op=op
                )
            except grpc.RpcError as exc:
                if _is_transient(exc) or _is_not_leader(exc):
                    # channel-level failure that outlived the whole
                    # retry/probe budget: the BASELINE IS KEPT (ISSUE
                    # 11 satellite) — nothing verifiably applied, so
                    # nulling _generation here would silently force a
                    # full resync on every transient blip; the next
                    # sync retries the delta and the continuity check
                    # below guards the ambiguous-apply case
                    raise
                if not baseline:
                    # nothing was delta-encoded; the failure is not
                    # recoverable by resending full state
                    self._invalidate()
                    raise
                # a restarted sidecar lost its resident tensors and refused
                # the delta frame — recoverable within the same cycle with
                # one full re-sync (ADVICE r5); a second failure is surfaced
                try:
                    reply = self._call_writer(
                        "sync", build(baseline, full=True), op=op
                    )
                    sent_full = True
                except grpc.RpcError:
                    self._invalidate()
                    raise
            epoch, gen = parse_snapshot_id(reply.snapshot_id)
            if self._generation is not None and not sent_full and (
                epoch != self._epoch or gen != self._generation + 1
            ):
                # another client synced in between, or the server restarted
                # (fresh epoch — the bare generation can coincidentally line
                # up after a restart, so the epoch check is load-bearing):
                # our deltas were applied onto a base we never saw.  Re-sync
                # full tensors — from the pre-clear baseline, so fields
                # omitted this cycle still resend their last acked state.
                try:
                    reply = self._call_writer(
                        "sync", build(baseline, full=True), op=op
                    )
                except grpc.RpcError:
                    # the server may have applied the full sync before
                    # failing; treat the baseline as unknown
                    self._invalidate()
                    raise
                epoch, gen = parse_snapshot_id(reply.snapshot_id)
            self._prev = dict(baseline, **staged)
            self._prev_scalars.update(staged_scalars)
            self._generation = gen
            self._epoch = epoch
            self.snapshot_id = reply.snapshot_id
            return reply

    # -- score / assign --
    def _call(self, stub, request, op=None):
        """Invoke Score/Assign; on FAILED_PRECONDITION (our snapshot was
        displaced by another client's Sync) invalidate the baseline so the
        caller's next sync() ships full state, then surface the error."""
        try:
            return self._traced_call(op, stub, request, self._timeout_s())
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.FAILED_PRECONDITION:
                self._invalidate()
            raise

    def _score_request(self, top_k: int, flat: bool = False):
        """One Score request with the propagated deadline budget and
        this client's band stamped on (ISSUE 13)."""
        return pb2.ScoreRequest(
            snapshot_id=self.snapshot_id or "", top_k=top_k, flat=flat,
            deadline_ms=int(self._deadline_ms), band=self.band,
        )

    def score(self, top_k: int = 0) -> List[List[Tuple[int, int]]]:
        reply = self._with_op(
            "score",
            lambda op: self._call_score(self._score_request(top_k), op=op),
        )
        return [
            list(zip(entry.node_index, entry.score)) for entry in reply.pods
        ]

    def score_flat(
        self, top_k: int = 0
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Flat top-k layout: (pod_index, counts, node_index, score) numpy
        arrays decoded straight from the packed reply bytes — the O(1)
        assembly path on both ends (round-3 review #8).  Entry group g
        (pod pod_index[g]) covers counts[g] consecutive entries."""
        reply = self._with_op(
            "score",
            lambda op: self._call_score(
                self._score_request(top_k, flat=True), op=op
            ),
        )
        # degraded visibility (ISSUE 13): True when the LAST flat Score
        # was served stale from the daemon's brownout cache while its
        # breaker was open — callers alarm on it instead of discovering
        # staleness in a placement graph
        self.last_degraded = bool(reply.degraded)
        if not reply.HasField("flat"):
            # a pre-flat server ignores the unknown request flag and sends
            # legacy lists; empty arrays here would read as "no feasible
            # node for any pod" — fail loudly instead
            raise RuntimeError(
                "scorer did not return the flat layout (server too old?); "
                "use score() for the legacy per-pod lists"
            )
        # .copy(): frombuffer over proto bytes is read-only; callers get
        # writable arrays like assign() returns
        return (
            np.frombuffer(reply.flat.pod_index, "<i4").copy(),
            np.frombuffer(reply.flat.counts, "<i4").copy(),
            np.frombuffer(reply.flat.node_index, "<i4").copy(),
            np.frombuffer(reply.flat.score, "<i8").copy(),
        )

    def assign(self) -> Tuple[np.ndarray, np.ndarray, float, str]:
        """Returns (assignment, status, cycle_ms, path); ``path`` names the
        device program that ran ("pallas"/"scan"/"shard") so callers can
        alarm on a degraded-path cycle instead of discovering it in a
        latency graph."""
        return self._with_op("assign", self._assign_op)

    def _assign_op(self, op=None):
        try:
            reply = self._call_writer(
                "assign",
                pb2.AssignRequest(
                    snapshot_id=self.snapshot_id or "",
                    deadline_ms=int(self._deadline_ms),
                    band=self.band,
                ),
                op=op,
            )
        except grpc.RpcError as e:
            # displaced snapshot (stale-id FAILED_PRECONDITION): the
            # baseline is gone — next sync ships full state.  The
            # "one writer" flavor CAN escape the probe when no replica
            # accepts writes inside the retry budget (leader dead,
            # nothing promoted yet) — that baseline is fine and must
            # survive, like the sync() transient path.
            if (
                e.code() == grpc.StatusCode.FAILED_PRECONDITION
                and not _is_not_leader(e)
            ):
                self._invalidate()
            raise
        return (
            np.asarray(reply.assignment, np.int32),
            np.asarray(reply.status, np.int32),
            reply.cycle_ms,
            reply.path,
        )
