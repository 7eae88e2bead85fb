"""Cycle telemetry for the TPU scoring pipeline (ISSUE 4).

Three layers, composed by :class:`CycleTelemetry` (one per
ScorerServicer, one per bridge daemon):

* **spans** (obs/spans.py) — a monotonic span recorder with explicit
  cycle ids ("c<epoch>-<seq>", correlating with "s<epoch>-<gen>"
  snapshot ids and echoed to clients in AssignReply.cycle_id).  Records
  host-side stages (Sync decode, delta scatter, dispatch, readback) and
  device-derived stats the solver already returns (rounds, path,
  wave_ms) — never from inside jitted code (koordlint's host-sync and
  span-leak rules gate the API statically).
* **metrics** (obs/scorer_metrics.py) — the koord_scorer_* Prometheus
  families over koordlet/metrics.py, served on the bridge daemon's
  /metrics (scheduler/server.py; MetricsRegistry.wsgi_app is the WSGI
  form).
* **flight** (obs/flight.py) — a ring buffer of the last K cycles'
  records + config knobs + snapshot ids, dumped as schema-validated
  JSON under --state-dir on cycle error or SIGUSR1.

The overhead contract is locked in by tests/test_resident_warm.py: a
warm delta-Sync/Assign stream with telemetry enabled (it always is on
the bridge) holds ZERO jit cache misses — instrumentation lives
entirely outside the traced programs.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

from koordinator_tpu.obs.export import (  # noqa: F401
    SpanExporter,
    resolve_export_dir,
)
from koordinator_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    validate_flight_dump,
)
from koordinator_tpu.obs.scorer_metrics import ScorerMetrics
from koordinator_tpu.obs.spans import (  # noqa: F401
    ClientTraceOp,
    CycleScope,
    SpanRecorder,
    TraceSpan,
)

logger = logging.getLogger(__name__)


def _config_knobs(cfg) -> Dict[str, object]:
    """The CycleConfig knobs worth reconstructing a bad cycle from."""
    if cfg is None:
        return {}
    return {
        "fit_scoring_strategy": getattr(cfg, "fit_scoring_strategy", ""),
        "enable_loadaware": bool(getattr(cfg, "enable_loadaware", False)),
        "enable_fit_score": bool(getattr(cfg, "enable_fit_score", False)),
    }


class CycleTelemetry:
    """Spans + scorer metrics + flight recorder, wired to the process
    feed of jit cache misses (analysis.retrace_guard)."""

    def __init__(
        self,
        epoch: str = "",
        cfg=None,
        state_dir: Optional[str] = None,
        capacity: int = 64,
        registry=None,
        trace_export: Optional[str] = None,
    ):
        self.spans = SpanRecorder(epoch=epoch)
        self.metrics = ScorerMetrics(registry=registry)
        self.registry = self.metrics.registry
        self.flight = FlightRecorder(
            capacity=capacity, state_dir=state_dir,
            config={"epoch": epoch, **_config_knobs(cfg)},
        )
        # distributed-trace export (ISSUE 14): completed TraceSpans
        # flow recorder -> trace_sink -> exporter as OTLP-shaped JSON
        # lines under the export dir ("<state-dir>/traces" by default
        # when --trace-export / KOORD_TRACE_EXPORT turns it on).  With
        # no exporter the sink still feeds the span-count family —
        # spans only exist when a client stamped a trace_id, so the
        # counter is exact either way.
        self.exporter: Optional[SpanExporter] = None
        directory = resolve_export_dir(trace_export, state_dir)
        if directory is not None:
            self.exporter = SpanExporter(
                directory,
                on_drop=self.metrics.count_trace_export_dropped,
            )
        self.spans.trace_sink = self._sink_trace_span
        self._unhooks = []
        self._install_feeds()

    def _sink_trace_span(self, record) -> None:
        self.metrics.count_trace_span(str(record.get("kind") or "unknown"))
        if self.exporter is not None:
            self.exporter.export(record)

    # -- process-wide feeds --
    def _install_feeds(self) -> None:
        # the listener closure must NOT hold self (or metrics) strongly:
        # watch_cache_misses keeps its callback for the life of the
        # process, and a strong cycle would pin every telemetry — and
        # its servicer — created by every test ever.  A weakref shim
        # no-ops and self-unhooks once the telemetry is collected.
        import weakref

        metrics_ref = weakref.ref(self.metrics)
        cell: Dict[str, object] = {}

        def _on_miss(kind: str) -> None:
            metrics = metrics_ref()
            if metrics is None:
                unhook = cell.pop("unhook", None)
                if unhook is not None:
                    unhook()
                return
            metrics.count_jit_miss(kind)

        try:
            from koordinator_tpu.analysis.retrace_guard import (
                watch_cache_misses,
            )

            cell["unhook"] = watch_cache_misses(_on_miss)
            self._unhooks.append(lambda: cell.pop("unhook", lambda: None)())
        except Exception:  # jax private monitoring API may drift; telemetry must degrade, not fail the server
            logger.warning(
                "jit cache-miss feed unavailable; "
                "koord_scorer_jit_cache_miss_total will not populate",
                exc_info=True,
            )

    def close(self) -> None:
        """Unhook the process-wide feeds (tests; daemons run for life)
        and close the span exporter handle."""
        for unhook in self._unhooks:
            try:
                unhook()
            except Exception:  # best-effort teardown; one failed unhook must not keep the rest hooked
                logger.warning("telemetry unhook failed", exc_info=True)
        self._unhooks = []
        if self.exporter is not None:
            self.exporter.close()

    # -- event sinks --
    def record_sync(self, info, snapshot_id: str, epoch: str,
                    generation: int) -> None:
        self.metrics.record_sync(info)
        self.metrics.set_snapshot(epoch, generation)
        spans = self.spans
        spans.current(snapshot_id=snapshot_id)
        spans.note("sync_path", info.get("path"))

    def commit_cycle(
        self,
        latency_ms: float,
        path: str,
        wave: int = 1,
        rounds: Optional[int] = None,
    ) -> Dict[str, object]:
        """Close the current cycle: metrics + flight ring."""
        self.metrics.observe_cycle(latency_ms, path, wave, rounds=rounds)
        spans = self.spans
        spans.note("path", path)
        spans.note("latency_ms", round(float(latency_ms), 3))
        if rounds is not None:
            spans.note("rounds", int(rounds))
        record = spans.commit()
        self.flight.record(record)
        return record

    # -- per-RPC scopes (ISSUE 6: exact records under concurrency) --
    def begin_rpc_scope(
        self,
        snapshot_id: Optional[str] = None,
        cycle_id: Optional[str] = None,
        adopt_pending: bool = True,
        trace_id: Optional[str] = None,
    ):
        """A private cycle for one RPC (see obs/spans.py CycleScope).
        The correlating RPC of a Sync→Score→Assign flow adopts the
        pending cycle atomically; concurrent siblings mint fresh ones
        and can no longer relabel or stamp it.  ``trace_id`` stamps
        the distributed-trace correlation onto the cycle record
        (ISSUE 14) so flight dumps and assembled trees cross-reference."""
        return self.spans.open_scope(
            snapshot_id=snapshot_id, cycle_id=cycle_id,
            adopt_pending=adopt_pending, trace_id=trace_id,
        )

    def commit_scope(
        self,
        scope,
        latency_ms: float,
        path: str,
        wave: int = 1,
        rounds: Optional[int] = None,
    ) -> Dict[str, object]:
        """`commit_cycle`, scoped: metrics + the scope's own record into
        the flight ring.  The recorder's pending cycle is untouched."""
        self.metrics.observe_cycle(latency_ms, path, wave, rounds=rounds)
        scope.note("path", path)
        scope.note("latency_ms", round(float(latency_ms), 3))
        if rounds is not None:
            scope.note("rounds", int(rounds))
        record = scope.commit()
        self.flight.record(record)
        return record

    def abort_scope(
        self, scope, stage: str, exc: BaseException, dump: bool = True
    ) -> None:
        """`abort_cycle`, scoped.  ``dump=False`` records the failed
        cycle in the ring without a disk dump — the client-protocol
        conditions (a displaced Assign) that must stay visible in the
        records but must not churn the dump directory."""
        if dump:
            self.metrics.count_cycle_error(stage)
        record = scope.commit(error=f"{stage}: {exc!r:.300}")
        self.flight.record(record)
        if dump:
            self.flight.dump("cycle-error")

    def abort_cycle(self, stage: str, exc: BaseException) -> None:
        """An UNEXPECTED failure on the cycle pipeline: count it, commit
        the partial record with the error attached, and dump the ring
        for the post-mortem.  Client-rejectable errors (a malformed
        frame bounced by validation) must NOT come here — they are
        counted via ``metrics.count_cycle_error`` alone, so a looping
        bad client can neither churn the ring/dump directory nor commit
        a pending cycle out from under another client's correlation."""
        self.metrics.count_cycle_error(stage)
        record = self.spans.commit(error=f"{stage}: {exc!r:.300}")
        self.flight.record(record)
        self.flight.dump("cycle-error")

    # Sync/Score-only streams (e.g. a non-leader replica whose Assign
    # is refused) never reach commit_cycle; without a backstop their
    # spans pile onto one immortal pending cycle and the flight ring
    # stays empty forever.  Past this many buffered spans the pending
    # cycle is committed as a backlog record at the next frame boundary.
    PENDING_COMMIT_SPANS = 64

    def flush_backlog(self) -> None:
        spans = self.spans
        # pending_spans() is atomic on the recorder (the coalescer's
        # batch leaders call this concurrently with Sync commits)
        if spans.pending_spans() >= self.PENDING_COMMIT_SPANS:
            spans.note("backlog", True)
            self.flight.record(spans.commit())
