"""bench.py budget accounting — stdlib only (no jax import).

A bench run once ended rc=124 with an EMPTY tail: the driver's axe landed before
the CPU-fallback artifact printed, because stage windows could overshoot
the total budget (an unclamped inter-probe sleep, and a 60s floor on the
CPU window applied even when less than 60s remained).  The invariant
locked in here: replaying ``parent()``'s exact window-request sequence
against ``_Budget`` — worst case, every stage consuming its full grant
and every probe retrying — the granted seconds sum to <= the budget for
ANY ``KOORD_BENCH_TOTAL_BUDGET``, and the CPU-fallback artifact stage
always receives a positive window whenever any budget remains.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_under_test", os.path.join(REPO, "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)

PROBE_TIMEOUT = 120.0
TPU_TIMEOUT = 600.0
CPU_TIMEOUT = 900.0


def _drain(total, probe_wait=2400.0):
    """Replay parent()'s window requests against _Budget with a fake
    clock that burns every granted second (the worst case the driver's
    timeout must survive).  Returns (granted windows, cpu window)."""
    now = [0.0]
    b = bench._Budget(
        total, reserve=CPU_TIMEOUT + 60.0, clock=lambda: now[0]
    )
    granted = []

    def spend(sec):
        granted.append(sec)
        now[0] += sec

    # _probe_until: repeated probe children + clamped inter-probe sleeps
    deadline = now[0] + b.window(probe_wait)
    while True:
        left = deadline - now[0]
        if left <= 0 or b.window(PROBE_TIMEOUT) <= 0:
            break
        spend(max(1.0, min(PROBE_TIMEOUT, left)))
        if now[0] >= deadline:
            break
        spend(min(30.0, deadline - now[0]))

    # up to three TPU attempts with a reprobe between retries
    for attempt, timeout in enumerate(
        (TPU_TIMEOUT, TPU_TIMEOUT, TPU_TIMEOUT * 3 // 4)
    ):
        w = b.window(timeout)
        if w <= 60:
            break
        spend(w)
        if attempt < 2:
            rw = b.window(PROBE_TIMEOUT)
            if rw <= 0:
                break
            spend(rw)

    # the CPU-fallback artifact stage (reserve released)
    cpu = b.window(CPU_TIMEOUT, reserve=0.0)
    if cpu > 0:
        spend(cpu)
    return granted, cpu


class TestBudgetInvariant:
    def test_windows_sum_to_at_most_the_budget(self):
        for total in (5.0, 30.0, 120.0, 600.0, 1200.0, 2400.0, 3600.0,
                      10000.0):
            granted, _ = _drain(total)
            assert sum(granted) <= total + 1e-6, (
                f"budget {total}: granted {sum(granted)} "
                f"across {len(granted)} windows"
            )

    def test_cpu_fallback_always_gets_a_window(self):
        # whatever the probe/TPU stages consumed, the artifact stage is
        # never starved: with any budget at all, the CPU child runs
        for total in (5.0, 30.0, 120.0, 2400.0, 10000.0):
            _, cpu = _drain(total)
            assert cpu > 0, f"budget {total}: cpu fallback starved"

    def test_full_cpu_slot_survives_the_probe_window(self):
        # in a normal-sized budget the reserve holds back a FULL CPU
        # slot even when probing and TPU attempts eat their maximum
        _, cpu = _drain(2400.0)
        assert cpu >= min(CPU_TIMEOUT, 60.0)

    def test_window_never_exceeds_remaining(self):
        now = [0.0]
        b = bench._Budget(100.0, reserve=30.0, clock=lambda: now[0])
        assert b.window(1000.0) <= 70.0
        now[0] = 90.0
        assert b.window(1000.0) <= 10.0
        assert b.window(1000.0, reserve=0.0) <= 10.0
        now[0] = 200.0
        assert b.window(1000.0, reserve=0.0) == 0.0


class TestArtifactDeadline:
    """ISSUE 5 satellite: the rc=124-no-artifact class, closed for
    real.  The budget accountant bounds the windows bench GRANTS
    itself, but a stage that hangs past its window — or a driver
    timeout shorter than the budget — used to kill the process with
    nothing on stdout.  The hard deadline replays here
    under an injected clock: a slow stage never returns, the watchdog
    fires, and a schema-valid artifact with ``"truncated": true`` is
    flushed before exit."""

    def _deadline(self, total=100.0, start=0.0):
        emitted = []
        fired = []
        now = [start]

        def sleep(s):
            # the injected clock IS the slow stage: every watchdog nap
            # burns fake seconds while the "stage" never completes
            now[0] += s

        d = bench._ArtifactDeadline(
            total,
            emit=lambda line: emitted.append(line) or True,
            clock=lambda: now[0],
            sleep=sleep,
            on_fire=lambda rc: fired.append(rc),
        )
        return d, emitted, fired, now

    def test_slow_stage_flushes_truncated_artifact(self):
        d, emitted, fired, _now = self._deadline(total=100.0)
        bench._PROGRESS["stage"] = "tpu_attempt_2"
        d.watch()  # fake clock: returns once the deadline elapsed
        assert fired == [1]
        assert len(emitted) == 1
        assert bench._validate_artifact(emitted[0]) == []
        doc = json.loads(emitted[0])
        assert doc["truncated"] is True
        assert "tpu_attempt_2" in doc["error"]

    def test_fire_is_idempotent(self):
        d, emitted, fired, _now = self._deadline()
        d.fire("SIGTERM from the driver")
        d.fire("hard wall-clock deadline reached before an artifact")
        assert fired == [1] and len(emitted) == 1
        assert "SIGTERM" in json.loads(emitted[0])["error"]

    def test_cancel_after_real_artifact_suppresses_the_flush(self):
        d, emitted, fired, _now = self._deadline()
        d.cancel()  # a real artifact line made it out
        d.fire("hard wall-clock deadline reached before an artifact")
        assert emitted == [] and fired == []

    def test_deadline_respects_margin(self):
        d, _e, _f, _now = self._deadline(total=100.0)
        assert d.deadline == pytest.approx(70.0)  # 30s margin
        # tiny budgets never go non-positive
        d2, _e, _f, _now = self._deadline(total=5.0)
        assert d2.deadline >= 1.0

    def test_truncated_artifact_line_is_schema_valid(self):
        d, _e, _f, _now = self._deadline()
        line = d.artifact_line("reason")
        assert bench._validate_artifact(line) == []


class TestArtifactSchemaTruncatedAndCoalesce:
    def _line(self, **extra):
        doc = {"metric": "m", "value": 1.0, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_truncated_must_be_bool(self):
        assert bench._validate_artifact(self._line(truncated=True)) == []
        assert bench._validate_artifact(self._line(truncated=False)) == []
        assert bench._validate_artifact(self._line(truncated="yes"))
        assert bench._validate_artifact(self._line(truncated=1))

    def test_concurrency_probe_fields(self):
        assert bench._validate_artifact(self._line(
            concurrency=8, coalesce_batch_mean=5.3,
            p50_score_ms=12.0, p99_score_ms=40.5,
            score_concurrent_speedup=4.2,
        )) == []
        assert bench._validate_artifact(self._line(concurrency=0))
        assert bench._validate_artifact(self._line(concurrency=True))
        assert bench._validate_artifact(self._line(coalesce_batch_mean=0.5))
        assert bench._validate_artifact(self._line(p99_score_ms=-1))
        assert bench._validate_artifact(
            self._line(score_concurrent_speedup=float("nan"))
        )

    def test_pipeline_probe_fields(self):
        # ISSUE 6: the pipelined-dispatch health fields must be archived
        # well-formed or not at all
        assert bench._validate_artifact(self._line(
            score_pipeline_speedup=2.1, device_idle_ms=0.4,
            coalesce_window_ms=1.5, launch_overlaps=37,
        )) == []
        assert bench._validate_artifact(self._line(
            device_idle_ms=None, coalesce_window_ms=None,
            score_pipeline_speedup=None, launch_overlaps=None,
        )) == []
        assert bench._validate_artifact(self._line(device_idle_ms=-1))
        assert bench._validate_artifact(
            self._line(coalesce_window_ms=float("inf"))
        )
        assert bench._validate_artifact(
            self._line(score_pipeline_speedup=float("nan"))
        )
        assert bench._validate_artifact(self._line(launch_overlaps=-3))
        assert bench._validate_artifact(self._line(launch_overlaps=True))
        assert bench._validate_artifact(self._line(launch_overlaps=1.5))

    def test_serial_sample_field(self):
        assert bench._validate_artifact(
            self._line(score_serial_sample=8)
        ) == []
        assert bench._validate_artifact(
            self._line(score_serial_sample=None)
        ) == []
        assert bench._validate_artifact(self._line(score_serial_sample=0))
        assert bench._validate_artifact(self._line(score_serial_sample=True))
        assert bench._validate_artifact(self._line(score_serial_sample=2.5))

    def test_serial_extrapolation(self):
        # the serialized baseline is one-request-at-a-time, so a sampled
        # storm wall scales linearly to the full request count — and a
        # full (or degenerate) sample passes through unchanged
        assert bench._extrapolate_serial(70.0, 8, 192) == 70.0 * 24
        assert bench._extrapolate_serial(70.0, 192, 192) == 70.0
        assert bench._extrapolate_serial(70.0, 0, 192) == 70.0
        assert bench._extrapolate_serial(70.0, 200, 192) == 70.0


class TestArtifactSchemaPluginFields:
    """ISSUE 15: the --config plugins fields — fused-vs-sequential
    term speedup, its wall components, and the term-enabled warm
    Score cost."""

    def _line(self, **extra):
        doc = {"metric": "plugins", "value": 1.0, "unit": "x"}
        doc.update(extra)
        return json.dumps(doc)

    def test_valid_plugin_fields_pass(self):
        assert bench._validate_artifact(self._line(
            plugin_terms=3,
            plugin_fused_speedup=2.1,
            plugin_fused_ms=5000.0,
            plugin_oracle_ms=7000.0,
            plugin_base_ms=3200.0,
            plugin_warm_score_ms=450.0,
        )) == []

    def test_malformed_plugin_fields_fail(self):
        assert bench._validate_artifact(self._line(plugin_terms=0))
        assert bench._validate_artifact(self._line(plugin_terms=True))
        assert bench._validate_artifact(self._line(plugin_terms=2.5))
        assert bench._validate_artifact(
            self._line(plugin_fused_speedup=-1.0)
        )
        assert bench._validate_artifact(
            self._line(plugin_fused_speedup=float("nan"))
        )
        assert bench._validate_artifact(self._line(plugin_fused_ms=-2))
        assert bench._validate_artifact(self._line(plugin_oracle_ms="x"))
        assert bench._validate_artifact(self._line(plugin_base_ms=-0.1))
        assert bench._validate_artifact(
            self._line(plugin_warm_score_ms=float("inf"))
        )

    def test_deadline_flush_covers_the_plugins_leg(self):
        # a deadline-flushed plugins artifact must validate: rc=124 on
        # the new config can never again mean "no artifact"
        emitted = []
        d = bench._ArtifactDeadline(
            1000.0, emit=emitted.append, metric="plugins"
        )
        line = d.artifact_line("timeout")
        assert json.loads(line)["metric"] == "plugins"
        assert json.loads(line)["truncated"] is True
        assert bench._validate_artifact(line) == []


class TestArtifactSchemaWaveFields:
    def _line(self, **extra):
        doc = {"metric": "m", "value": 1.0, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_valid_wave_fields_pass(self):
        assert bench._validate_artifact(self._line(wave=32, rounds=500)) == []
        # the wave stage is best-effort: null rounds is a legal artifact
        assert bench._validate_artifact(self._line(wave=32, rounds=None)) == []

    def test_malformed_wave_fields_fail(self):
        assert bench._validate_artifact(self._line(wave=0))
        assert bench._validate_artifact(self._line(wave=True))
        assert bench._validate_artifact(self._line(wave="32"))
        assert bench._validate_artifact(self._line(rounds=-1))
        assert bench._validate_artifact(self._line(rounds=1.5))


class TestTreeArtifactFields:
    """ISSUE 18: the relay-tree config's artifact fields — depth,
    fan-out amplification, leaf-storm speedup, the zero-resync chaos
    counters and the autoscale verdict — must be archived
    schema-valid; malformed ones must not pass as measurements."""

    def _line(self, **extra):
        doc = {"metric": "tree_converge_wall_ms", "value": 1.0,
               "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_valid_tree_fields_pass(self):
        assert bench._validate_artifact(self._line(
            tree_depth=3, tree_fanout_amplification=2.0,
            tree_read_speedup=0.47, frames_per_wakeup=1.0,
            resyncs_during_failover=0, full_opens_during_failover=0,
            ancestor_switches=1, compressed_fulls=4,
            autoscale_scale_ups=3, autoscale_scale_downs=2,
            autoscale_peak_replicas=4, autoscale_slo_held=True,
        )) == []
        # a truncated (deadline-flushed) artifact may carry nulls
        assert bench._validate_artifact(self._line(
            tree_depth=None, tree_read_speedup=None,
            autoscale_slo_held=None,
        )) == []

    def test_malformed_tree_depth_fails(self):
        assert bench._validate_artifact(self._line(tree_depth=0))
        assert bench._validate_artifact(self._line(tree_depth=True))
        assert bench._validate_artifact(self._line(tree_depth="3"))

    def test_malformed_ratios_fail(self):
        assert bench._validate_artifact(
            self._line(tree_fanout_amplification=-1.0)
        )
        assert bench._validate_artifact(
            self._line(tree_read_speedup=float("nan"))
        )
        assert bench._validate_artifact(
            self._line(frames_per_wakeup=float("inf"))
        )

    def test_malformed_counts_and_verdict_fail(self):
        assert bench._validate_artifact(self._line(ancestor_switches=-1))
        assert bench._validate_artifact(
            self._line(full_opens_during_failover=1.5)
        )
        assert bench._validate_artifact(self._line(compressed_fulls=True))
        assert bench._validate_artifact(
            self._line(autoscale_peak_replicas="4")
        )
        assert bench._validate_artifact(
            self._line(autoscale_slo_held="yes")
        )

    def test_tree_is_a_known_config(self):
        import inspect

        src = inspect.getsource(bench.child_config)
        assert 'config == "tree"' in src


class TestArtifactSchemaSpans:
    """ISSUE 4: BENCH_*.json trajectories carry per-stage span
    summaries; a stage that measured nothing publishes null, and a
    malformed breakdown must not be archived as a measurement."""

    def _line(self, **extra):
        doc = {"metric": "m", "value": 1.0, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_valid_spans_pass(self):
        assert bench._validate_artifact(self._line(spans={})) == []
        assert bench._validate_artifact(
            self._line(spans={"init": 12.5, "compile": 1500, "wave": None})
        ) == []
        # spans are optional: a span-less artifact stays valid
        assert bench._validate_artifact(self._line()) == []

    def test_malformed_spans_fail(self):
        assert bench._validate_artifact(self._line(spans=[1, 2]))
        assert bench._validate_artifact(self._line(spans={"init": "fast"}))
        assert bench._validate_artifact(self._line(spans={"init": True}))
        assert bench._validate_artifact(self._line(spans={"init": -1.0}))
        assert bench._validate_artifact(
            self._line(spans={"init": float("nan")})
        )
        assert bench._validate_artifact(self._line(spans={"": 1.0}))

    def test_headline_child_seeds_every_stage_null_safe(self):
        """The headline child pre-seeds its span keys so a crashed
        best-effort leg shows as null, not as a missing key a reader
        would misread as 'stage did not exist'."""
        import inspect

        src = inspect.getsource(bench.child)
        for key in ("lowering_probe", "cpu_native_mt"):
            assert f'"{key}": None' in src


class TestTraceArtifactFields:
    """ISSUE 12: the trace-replay SLO-gate fields must be archived
    well-formed or not at all, and a deadline-killed trace replay must
    still flush one schema-valid ``"truncated": true`` artifact."""

    def _line(self, **extra):
        doc = {"metric": "trace_cycle_p99_ms", "value": 9.9, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def _verdict(self, **over):
        doc = {"name": "koord-prod-cycle-p99", "quantile": 0.99,
               "threshold_ms": 2500.0, "observed_ms": 12.5,
               "count": 5, "ok": True}
        doc.update(over)
        return doc

    def test_valid_trace_fields_pass(self):
        assert bench._validate_artifact(self._line(
            trace_events=48,
            trace_parity_checks=49,
            trace_retraces=0,
            trace_seed=0,
            trace_digest="abc123",
            trace_band_p99_ms={"koord-prod": 12.5, "infra": None},
            trace_rpc_p99_ms={"sync": 1.0, "score": 3.0},
            trace_slo=[self._verdict(),
                       self._verdict(ok=False, observed_ms=None)],
            trace_slo_pass=True,
        )) == []
        # every trace field is optional (other configs omit them all)
        assert bench._validate_artifact(self._line()) == []

    def test_malformed_counts_fail(self):
        assert bench._validate_artifact(self._line(trace_events=-1))
        assert bench._validate_artifact(self._line(trace_events=True))
        assert bench._validate_artifact(self._line(trace_retraces=1.5))
        assert bench._validate_artifact(self._line(trace_parity_checks="x"))
        assert bench._validate_artifact(self._line(trace_digest=""))
        assert bench._validate_artifact(self._line(trace_slo_pass="yes"))

    def test_malformed_band_maps_fail(self):
        assert bench._validate_artifact(self._line(trace_band_p99_ms=[1]))
        assert bench._validate_artifact(
            self._line(trace_band_p99_ms={"prod": -1})
        )
        assert bench._validate_artifact(
            self._line(trace_rpc_p99_ms={"sync": float("inf")})
        )
        assert bench._validate_artifact(
            self._line(trace_band_p99_ms={"": 1.0})
        )

    def test_malformed_verdicts_fail(self):
        assert bench._validate_artifact(self._line(trace_slo={}))
        assert bench._validate_artifact(self._line(trace_slo=[[]]))
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(name="")])
        )
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(ok="yes")])
        )
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(quantile=0.0)])
        )
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(quantile=1.5)])
        )
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(threshold_ms=-5)])
        )
        assert bench._validate_artifact(
            self._line(trace_slo=[self._verdict(observed_ms=float("nan"))])
        )

    def test_trace_overhead_and_assembly_fields(self):
        """ISSUE 14: the tracing-overhead delta and the assembly counts
        are schema-validated artifact fields — the overhead may be
        NEGATIVE (run noise) but never below -100 or non-finite, and
        the counts are plain non-negative ints."""
        assert bench._validate_artifact(self._line(
            trace_overhead_p99_pct=2.4,
            assembled_traces=97,
            orphan_spans=0,
        )) == []
        assert bench._validate_artifact(self._line(
            trace_overhead_p99_pct=-3.1,  # traced run won the noise
        )) == []
        assert bench._validate_artifact(
            self._line(trace_overhead_p99_pct=float("nan"))
        )
        assert bench._validate_artifact(
            self._line(trace_overhead_p99_pct=-250.0)
        )
        assert bench._validate_artifact(
            self._line(trace_overhead_p99_pct="small")
        )
        assert bench._validate_artifact(self._line(assembled_traces=-1))
        assert bench._validate_artifact(self._line(assembled_traces=True))
        assert bench._validate_artifact(self._line(orphan_spans=0.5))

    def test_deadline_killed_trace_replay_flushes_truncated_artifact(self):
        """The _ArtifactDeadline flush path covers --config trace: a
        replay hanging past the budget (a wedged UDS server, a compile
        storm) must still put ONE schema-valid truncated artifact on
        stdout, stamped with the trace stage it died in — the
        rc=124-no-artifact class must not reopen for the new
        config."""
        emitted, fired = [], []
        now = [0.0]

        def sleep(s):
            now[0] += s

        d = bench._ArtifactDeadline(
            100.0,
            emit=lambda line: emitted.append(line) or True,
            clock=lambda: now[0],
            sleep=sleep,
            on_fire=lambda rc: fired.append(rc),
            metric="trace",  # main() arms the deadline with args.config
        )
        old_stage = bench._PROGRESS["stage"]
        try:
            bench._PROGRESS["stage"] = "config_trace_cpu"
            d.watch()
        finally:
            bench._PROGRESS["stage"] = old_stage
        assert fired == [1] and len(emitted) == 1
        assert bench._validate_artifact(emitted[0]) == []
        doc = json.loads(emitted[0])
        assert doc["truncated"] is True
        assert doc["metric"] == "trace"
        assert "config_trace_cpu" in doc["error"]


class TestChaosTraceArtifactFields:
    """ISSUE 13: the chaos x trace gate fields must be archived
    well-formed or not at all, and a deadline-killed chaos-trace run
    must still flush one schema-valid truncated artifact."""

    def _line(self, **extra):
        doc = {"metric": "chaos_trace_recovery_ms", "value": 6.6,
               "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def _verdict(self, **over):
        doc = {"name": "recovery-p99", "quantile": 0.99,
               "threshold_ms": 5000.0, "observed_ms": 9.9,
               "count": 1, "ok": True}
        doc.update(over)
        return doc

    def test_valid_chaos_trace_fields_pass(self):
        assert bench._validate_artifact(self._line(
            chaos_trace_events=24,
            chaos_trace_seed=0,
            chaos_trace_errors=4,
            chaos_trace_retraces=0,
            chaos_trace_digest="abc123",
            degraded_replies=1,
            breaker_trips=1,
            recovery_ms=6.6,
            shed_by_band={"koord-free": 96, "none": 2},
            storm_band_p99_ms={"koord-prod": 49.7, "koord-free": None},
            chaos_trace_slo=[self._verdict(),
                             self._verdict(ok=False, observed_ms=None)],
            chaos_trace_slo_pass=True,
        )) == []
        # every chaos-trace field is optional (other configs omit them)
        assert bench._validate_artifact(self._line()) == []

    def test_malformed_counts_fail(self):
        assert bench._validate_artifact(self._line(chaos_trace_events=-1))
        assert bench._validate_artifact(self._line(degraded_replies=1.5))
        assert bench._validate_artifact(self._line(breaker_trips=True))
        assert bench._validate_artifact(self._line(recovery_ms=-1))
        assert bench._validate_artifact(
            self._line(recovery_ms=float("nan"))
        )
        assert bench._validate_artifact(self._line(chaos_trace_digest=""))
        assert bench._validate_artifact(
            self._line(chaos_trace_slo_pass="yes")
        )

    def test_malformed_shed_by_band_fails(self):
        assert bench._validate_artifact(self._line(shed_by_band=[1]))
        assert bench._validate_artifact(
            self._line(shed_by_band={"koord-free": -1})
        )
        assert bench._validate_artifact(
            self._line(shed_by_band={"koord-free": 1.5})
        )
        assert bench._validate_artifact(
            self._line(shed_by_band={"": 3})
        )
        assert bench._validate_artifact(
            self._line(storm_band_p99_ms={"koord-prod": -1})
        )

    def test_malformed_verdicts_fail(self):
        assert bench._validate_artifact(self._line(chaos_trace_slo={}))
        assert bench._validate_artifact(
            self._line(chaos_trace_slo=[self._verdict(name="")])
        )
        assert bench._validate_artifact(
            self._line(chaos_trace_slo=[self._verdict(quantile=0.0)])
        )
        assert bench._validate_artifact(
            self._line(chaos_trace_slo=[self._verdict(threshold_ms=-5)])
        )

    def test_deadline_killed_chaos_trace_flushes_truncated_artifact(self):
        """The _ArtifactDeadline truncated-flush path covers --config
        chaos-trace: a run wedged mid-chaos (a kill that never
        recovers, a hung storm thread) must still put ONE schema-valid
        truncated artifact on stdout stamped with the stage it died
        in."""
        emitted, fired = [], []
        now = [0.0]

        def sleep(s):
            now[0] += s

        d = bench._ArtifactDeadline(
            100.0,
            emit=lambda line: emitted.append(line) or True,
            clock=lambda: now[0],
            sleep=sleep,
            on_fire=lambda rc: fired.append(rc),
            metric="chaos-trace",  # main() arms it with args.config
        )
        old_stage = bench._PROGRESS["stage"]
        try:
            bench._PROGRESS["stage"] = "config_chaos-trace_cpu"
            d.watch()
        finally:
            bench._PROGRESS["stage"] = old_stage
        assert fired == [1] and len(emitted) == 1
        assert bench._validate_artifact(emitted[0]) == []
        doc = json.loads(emitted[0])
        assert doc["truncated"] is True
        assert doc["metric"] == "chaos-trace"
        assert "config_chaos-trace_cpu" in doc["error"]


class TestSparseArtifactFields:
    """ISSUE 16 satellite: the sparse candidate-scoring probe fields
    archive well-formed or not at all — including the one non-numeric
    legal value, the literal ``"OOM"`` that records the dense engine
    REFUSING to allocate its [P, N] tensors at the headline scale."""

    def _line(self, **extra):
        doc = {"metric": "sparse_score_ms", "value": 59.5, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_full_sparse_artifact_with_dense_oom_is_valid(self):
        assert bench._validate_artifact(self._line(
            sparse_score_ms=59.5, sparse_build_ms=36900.0,
            dense_score_ms="OOM", sparse_speedup=6.4,
            candidate_width=256, candidate_refresh_total=10,
        )) == []

    def test_both_engines_measured_is_valid(self):
        assert bench._validate_artifact(self._line(
            sparse_score_ms=19.7, dense_score_ms=17217.0,
            sparse_speedup=873.9, candidate_width=256,
            candidate_refresh_total=9,
        )) == []

    def test_dense_score_ms_rejects_everything_but_oom_or_number(self):
        assert bench._validate_artifact(self._line(dense_score_ms=None)) == []
        assert bench._validate_artifact(self._line(dense_score_ms=0)) == []
        assert bench._validate_artifact(self._line(dense_score_ms="oom"))
        assert bench._validate_artifact(self._line(dense_score_ms="fast"))
        assert bench._validate_artifact(self._line(dense_score_ms=-1))
        assert bench._validate_artifact(
            self._line(dense_score_ms=float("nan"))
        )

    def test_sparse_timings_must_be_finite_nonneg(self):
        assert bench._validate_artifact(self._line(sparse_score_ms=-0.1))
        assert bench._validate_artifact(
            self._line(sparse_build_ms=float("inf"))
        )
        assert bench._validate_artifact(self._line(sparse_speedup=-2.0))
        assert bench._validate_artifact(
            self._line(sparse_speedup=float("nan"))
        )

    def test_candidate_width_must_be_a_positive_int(self):
        assert bench._validate_artifact(self._line(candidate_width=256)) == []
        assert bench._validate_artifact(self._line(candidate_width=0))
        assert bench._validate_artifact(self._line(candidate_width=True))
        assert bench._validate_artifact(self._line(candidate_width=64.0))

    def test_candidate_refresh_total_must_be_a_nonneg_int(self):
        assert bench._validate_artifact(
            self._line(candidate_refresh_total=0)
        ) == []
        assert bench._validate_artifact(
            self._line(candidate_refresh_total=-1)
        )
        assert bench._validate_artifact(
            self._line(candidate_refresh_total=True)
        )
        assert bench._validate_artifact(
            self._line(candidate_refresh_total=9.5)
        )

    def test_deadline_killed_sparse_run_flushes_truncated_artifact(self):
        """A sparse run wedged mid-build (the blocked sweep at the
        headline node count is the slow stage) still puts ONE
        schema-valid truncated artifact on stdout stamped with the
        stage it died in."""
        emitted, fired = [], []
        now = [0.0]

        def sleep(s):
            now[0] += s

        d = bench._ArtifactDeadline(
            100.0,
            emit=lambda line: emitted.append(line) or True,
            clock=lambda: now[0],
            sleep=sleep,
            on_fire=lambda rc: fired.append(rc),
            metric="sparse",  # main() arms it with args.config
        )
        old_stage = bench._PROGRESS["stage"]
        try:
            bench._PROGRESS["stage"] = "config_sparse_cpu"
            d.watch()
        finally:
            bench._PROGRESS["stage"] = old_stage
        assert fired == [1] and len(emitted) == 1
        assert bench._validate_artifact(emitted[0]) == []
        doc = json.loads(emitted[0])
        assert doc["truncated"] is True
        assert doc["metric"] == "sparse"
        assert "config_sparse_cpu" in doc["error"]


class TestArtifactSchemaDevprofFields:
    """ISSUE 19: the device-time-truth fields — the launch ledger's
    compile-vs-device split, the attributed backend, the sampling
    overhead delta and the parent's TPU-probe verdict.  The archive
    rule is unchanged: malformed values must not be archived, nulls
    (probe failed / ledger off) always pass."""

    def _line(self, **extra):
        doc = {"metric": "m", "value": 1.0, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_well_formed_devprof_fields_pass(self):
        assert bench._validate_artifact(self._line(
            devprof_backend="cpu", devprof_compiles=7,
            devprof_compile_ms_total=412.6, devprof_device_score_us=83.2,
            devprof_flops_per_launch=1.2e9, devprof_overhead_p99_pct=0.7,
            tpu_probe="unreachable",
        )) == []

    def test_all_null_devprof_fields_pass(self):
        assert bench._validate_artifact(self._line(
            devprof_backend=None, devprof_compiles=None,
            devprof_compile_ms_total=None, devprof_device_score_us=None,
            devprof_flops_per_launch=None, devprof_overhead_p99_pct=None,
            tpu_probe=None,
        )) == []

    def test_malformed_compile_and_device_fields_fail(self):
        assert bench._validate_artifact(
            self._line(devprof_compile_ms_total=-1)
        )
        assert bench._validate_artifact(
            self._line(devprof_compile_ms_total=float("nan"))
        )
        assert bench._validate_artifact(
            self._line(devprof_device_score_us=float("inf"))
        )
        assert bench._validate_artifact(
            self._line(devprof_device_score_us="83")
        )
        assert bench._validate_artifact(
            self._line(devprof_flops_per_launch=-2.0)
        )

    def test_backend_and_compiles_shape(self):
        assert bench._validate_artifact(self._line(devprof_backend=""))
        assert bench._validate_artifact(self._line(devprof_backend=3))
        assert bench._validate_artifact(self._line(devprof_compiles=-1))
        assert bench._validate_artifact(self._line(devprof_compiles=True))
        assert bench._validate_artifact(self._line(devprof_compiles=2.5))

    def test_overhead_delta_rule_matches_trace_overhead(self):
        # negative is legitimate run noise; below -100 is fabricated
        assert bench._validate_artifact(
            self._line(devprof_overhead_p99_pct=-3.0)
        ) == []
        assert bench._validate_artifact(
            self._line(devprof_overhead_p99_pct=-101.0)
        )
        assert bench._validate_artifact(
            self._line(devprof_overhead_p99_pct=float("nan"))
        )

    def test_tpu_probe_field_shape(self):
        assert bench._validate_artifact(self._line(tpu_probe="live")) == []
        assert bench._validate_artifact(
            self._line(tpu_probe="live-then-lost")
        ) == []
        assert bench._validate_artifact(self._line(tpu_probe=""))
        assert bench._validate_artifact(self._line(tpu_probe=7))

    def test_stamp_tpu_probe_rides_the_artifact(self):
        # the r04/r05 fix: the parent's probe verdict is stamped onto
        # whatever the child printed, and an unparseable line passes
        # through untouched for the validator to reject downstream
        line = bench._stamp_tpu_probe(self._line(), "unreachable")
        doc = json.loads(line)
        assert doc["tpu_probe"] == "unreachable"
        assert bench._validate_artifact(line) == []
        assert bench._stamp_tpu_probe("not json{", "live") == "not json{"
        assert bench._stamp_tpu_probe(None, "live") is None

    def test_deadline_flush_still_valid_with_devprof_schema(self):
        """The truncated-flush line must stay schema-valid now that the
        validator knows the devprof fields (the deadline artifact
        carries none of them — all-absent must read as all-null)."""
        emitted, fired = [], []
        now = [0.0]

        def sleep(s):
            now[0] += s

        d = bench._ArtifactDeadline(
            100.0,
            emit=lambda line: emitted.append(line) or True,
            clock=lambda: now[0],
            sleep=sleep,
            on_fire=lambda rc: fired.append(rc),
        )
        old_stage = bench._PROGRESS["stage"]
        try:
            bench._PROGRESS["stage"] = "devprof_storm"
            d.watch()
        finally:
            bench._PROGRESS["stage"] = old_stage
        assert fired == [1] and len(emitted) == 1
        assert bench._validate_artifact(emitted[0]) == []
        doc = json.loads(emitted[0])
        assert doc["truncated"] is True
        assert "devprof_storm" in doc["error"]


class TestArtifactSchemaColdstartFields:
    """ISSUE 20: the cold-path economics fields — the coldstart leg's
    boot walls and prewarm stats, the failover warm-restart
    replay/compile split, and the autoscaler's spawn->ready wall.
    Nulls always pass (leg not run / attribution unavailable);
    malformed values must not be archived."""

    def _line(self, **extra):
        doc = {"metric": "m", "value": 1.0, "unit": "ms"}
        doc.update(extra)
        return json.dumps(doc)

    def test_well_formed_coldstart_fields_pass(self):
        assert bench._validate_artifact(self._line(
            cold_start_ms=1995.1, warm_cache_start_ms=840.3,
            cold_start_speedup=2.37, prewarm_ms=530.3,
            prewarm_signatures=12, prewarm_compiled=11,
            prewarm_compile_ms=263.4, cold_build_serial_ms=37000.0,
            cold_build_ms=14800.0, cold_build_speedup=2.5,
            build_nodes=2097152,
        )) == []

    def test_all_null_coldstart_fields_pass(self):
        assert bench._validate_artifact(self._line(
            cold_start_ms=None, warm_cache_start_ms=None,
            cold_start_speedup=None, prewarm_ms=None,
            prewarm_signatures=None, prewarm_compiled=None,
            prewarm_compile_ms=None, cold_build_serial_ms=None,
            cold_build_ms=None, cold_build_speedup=None,
            build_nodes=None,
        )) == []

    def test_malformed_boot_walls_fail(self):
        assert bench._validate_artifact(self._line(cold_start_ms=-1))
        assert bench._validate_artifact(
            self._line(warm_cache_start_ms=float("nan"))
        )
        assert bench._validate_artifact(
            self._line(cold_start_speedup=float("inf"))
        )
        assert bench._validate_artifact(self._line(prewarm_ms=-0.5))

    def test_malformed_build_timings_fail(self):
        assert bench._validate_artifact(
            self._line(cold_build_serial_ms=-3.0)
        )
        assert bench._validate_artifact(
            self._line(cold_build_ms=float("nan"))
        )
        assert bench._validate_artifact(
            self._line(cold_build_speedup=-1.0)
        )

    def test_prewarm_counts_must_be_nonneg_ints(self):
        for key in ("prewarm_signatures", "prewarm_compiled",
                    "build_nodes"):
            assert bench._validate_artifact(self._line(**{key: 0})) == []
            assert bench._validate_artifact(self._line(**{key: -1}))
            assert bench._validate_artifact(self._line(**{key: True}))
            assert bench._validate_artifact(self._line(**{key: 2.5}))

    def test_failover_restart_split_fields(self):
        assert bench._validate_artifact(self._line(
            restart_replay_ms=12.4, restart_compile_ms=310.9
        )) == []
        assert bench._validate_artifact(self._line(
            restart_replay_ms=None, restart_compile_ms=None
        )) == []
        assert bench._validate_artifact(
            self._line(restart_replay_ms=-1.0)
        )
        assert bench._validate_artifact(
            self._line(restart_compile_ms=float("nan"))
        )

    def test_spawn_to_ready_field(self):
        assert bench._validate_artifact(
            self._line(spawn_to_ready_ms=41.2)
        ) == []
        assert bench._validate_artifact(
            self._line(spawn_to_ready_ms=None)
        ) == []
        assert bench._validate_artifact(
            self._line(spawn_to_ready_ms=-0.1)
        )
        assert bench._validate_artifact(
            self._line(spawn_to_ready_ms=float("inf"))
        )

    def test_coldstart_is_a_dispatchable_config(self):
        # the driver archives per-config: a choice missing from the
        # inline parser would make the leg silently unrunnable
        import inspect

        src = inspect.getsource(bench.main)
        assert '"coldstart"' in src
        assert "--coldstart-server" in src
