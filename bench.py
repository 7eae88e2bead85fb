#!/usr/bin/env python
"""Benchmark: full batched scheduling cycle, 10k pods x 2k nodes (BASELINE
config #4: ElasticQuota multi-tenant + LS/BE mix).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N, ...}

``vs_baseline`` is the north-star target (500 ms on one TPU v5e-1, from
/root/repo/BASELINE.json — the reference publishes no numbers) divided by
the measured wall-clock: > 1.0 means the target is beaten.

Robustness (``import jax``/``jax.devices()`` can HANG outright when a
TPU backend is unhealthy):

* the parent process (this script, stdlib only — it never imports jax)
  runs the measurement in a CHILD process under a hard timeout;
* TPU attempts are retried with backoff; if the backend never comes up the
  bench falls back to a single-device virtual-CPU run of the same cycle
  (scan path) so an artifact always exists (``backend`` records the truth);
* the child separates compile time from steady-state time and records
  which code path executed (``path``: "pallas" single-kernel cycle vs
  "scan" lax.scan) — on TPU the Pallas kernel is asserted, NO silent
  fallback;
* any failure prints a JSON error line (never a bare stack trace);
* every artifact line is schema-validated before printing
  (``_validate_artifact``): a crashed stage exits non-zero instead of
  publishing a partial BENCH_*.json line.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional

TARGET_MS = 500.0
PODS, NODES = 10_000, 2_000
METRIC = "sched_cycle_10kpod_2knode_ms"

# NOTE: the child pins the platform with jax.config.update before any
# backend touch when --platform cpu is passed (an env var alone can be
# overridden by the environment).
_CPU_ENV = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
# the mesh config's CPU leg needs a multi-device virtual platform (the
# forced-host analog of an 8-chip slice) so the sharded snapshot
# actually spreads; every other config keeps the single-device CPU env
_MESH_CPU_ENV = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
# backend-init probes are cheap and discriminate "backend dead" (skip
# straight to CPU) from "compile slow" (give the TPU run its full budget)
PROBE_TIMEOUT = int(os.environ.get("KOORD_BENCH_PROBE_TIMEOUT", "120"))
TPU_TIMEOUT = int(os.environ.get("KOORD_BENCH_TPU_TIMEOUT", "600"))
CPU_TIMEOUT = int(os.environ.get("KOORD_BENCH_CPU_TIMEOUT", "900"))
# Artifact-first wall-clock budget (a run once ended rc=124 with NO artifact:
# the 2400s TPU probe window plus the CPU fallback overran the driver's
# timeout).  Every stage's window is derived from what remains of this
# budget, and the CPU fallback is always reserved a slot — an artifact
# line exists under every failure mode before the driver's axe falls.
# 1140s (not 2400s): the driver's own deadline is ~20 minutes, so the
# whole run — probe + at most one TPU attempt + the reserved CPU
# fallback — must complete, artifact on stdout, before that axe; the
# _ArtifactDeadline watchdog flushes a truncated-but-parseable line 30s
# before this budget elapses as the last line of defense.
TOTAL_BUDGET = 1140.0  # default for KOORD_BENCH_TOTAL_BUDGET, seconds

# Best-known progress of the parent process, mutated as stages run and
# read by the hard-deadline/SIGTERM flush (_ArtifactDeadline): when the
# axe falls mid-stage, the truncated artifact says WHERE.
_PROGRESS = {"stage": "start", "errors": []}


class _ArtifactDeadline:
    """Hard wall-clock deadline for the WHOLE bench process (the real
    fix for the rc=124-no-artifact class: the budget
    accountant bounds the windows bench grants itself, but a stage that
    HANGS past its window — or a driver timeout shorter than the budget
    — used to kill the process with nothing on stdout).  Two triggers,
    one idempotent flush:

    * a daemon watchdog thread fires ``margin_s`` before the configured
      budget elapses and emits a schema-valid artifact line with
      ``"truncated": true`` plus the last stage reached, then exits;
    * a SIGTERM handler (the first signal ``timeout`` sends) does the
      same immediately, covering drivers whose deadline is SHORTER than
      ours.

    ``clock``/``sleep``/``on_fire`` are injectable so the stdlib-only
    regression test (tests/test_bench_budget.py) can replay a slow
    stage under a fake clock without waiting wall time."""

    def __init__(self, total_s: float, emit=None, margin_s: float = 30.0,
                 clock=time.monotonic, sleep=time.sleep, on_fire=None,
                 metric: str = METRIC):
        self._emit = emit or _emit_artifact
        self._clock = clock
        self._sleep = sleep
        self._on_fire = on_fire or (lambda rc: os._exit(rc))
        self._metric = metric
        self.deadline = clock() + max(1.0, total_s - margin_s)
        self._fired = threading.Lock()  # acquired once, never released

    def artifact_line(self, reason: str) -> str:
        return json.dumps(
            {
                "metric": self._metric,
                "value": -1,
                "unit": "ms",
                "vs_baseline": 0.0,
                "truncated": True,
                "error": (
                    f"{reason}; last stage: {_PROGRESS['stage']}"
                    + (
                        "; " + "; ".join(_PROGRESS["errors"][-2:])
                        if _PROGRESS["errors"]
                        else ""
                    )
                ),
            }
        )

    def fire(self, reason: str) -> None:
        """Flush the truncated artifact exactly once, then exit(1).
        ``os._exit`` (not sys.exit): the main thread may be blocked in
        subprocess.run and must not get a chance to swallow the exit."""
        if not self._fired.acquire(blocking=False):
            return
        self._emit(self.artifact_line(reason))
        sys.stdout.flush()
        self._on_fire(1)

    def cancel(self) -> None:
        """A real artifact made it out: the flush must never fire."""
        self._fired.acquire(blocking=False)

    def watch(self) -> None:
        while True:
            left = self.deadline - self._clock()
            if left <= 0:
                break
            self._sleep(min(left, 1.0))
        self.fire("hard wall-clock deadline reached before an artifact")

    def install(self) -> "_ArtifactDeadline":
        threading.Thread(target=self.watch, daemon=True).start()
        try:
            signal.signal(
                signal.SIGTERM,
                lambda signum, frame: self.fire("SIGTERM from the driver"),
            )
        except ValueError:
            pass  # non-main thread (tests); the watchdog still covers us
        return self


def _bad_finite_nonneg(v, minimum: float = 0.0) -> bool:
    """True when ``v`` is NOT a finite number >= ``minimum`` (bools
    excluded) — the one numeric-acceptance rule every per-field check
    in ``_validate_artifact`` shares."""
    return (
        isinstance(v, bool)
        or not isinstance(v, (int, float))
        or v != v
        or v in (float("inf"), float("-inf"))
        or v < minimum
    )


def _validate_artifact(line: Optional[str]) -> list:
    """Small schema over the one BENCH_*.json line: a crashed or
    half-finished stage must not publish a partial artifact the driver
    would archive as a measurement.  Returns problems (empty = valid)."""
    try:
        doc = json.loads(line or "")
    except ValueError:
        return ["artifact is not valid JSON"]
    if not isinstance(doc, dict):
        return ["artifact is not a JSON object"]
    problems = []
    metric = doc.get("metric")
    if not isinstance(metric, str) or not metric:
        problems.append("'metric' must be a non-empty string")
    value = doc.get("value")
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        problems.append("'value' must be a number")
    elif value != value or value in (float("inf"), float("-inf")):
        problems.append("'value' must be finite")
    if "error" in doc and not isinstance(doc["error"], str):
        problems.append("'error' must be a string")
    # a deadline-flushed partial artifact must SAY so, and say it as a
    # real boolean — "truncated": "maybe" is not a measurement state
    if "truncated" in doc and not isinstance(doc["truncated"], bool):
        problems.append("'truncated' must be a boolean")
    if "error" not in doc:
        # a real measurement also names its unit; error artifacts may not
        unit = doc.get("unit")
        if not isinstance(unit, str) or not unit:
            problems.append("'unit' must be a non-empty string")
    vsb = doc.get("vs_baseline")
    if vsb is not None and (
        isinstance(vsb, bool)
        or not isinstance(vsb, (int, float))
        or vsb != vsb
        or vsb in (float("inf"), float("-inf"))
    ):
        problems.append("'vs_baseline' must be a finite number")
    # wave-batched cycle fields (ISSUE 3): the round count is the win the
    # artifact publishes, so a malformed one must not be archived
    wv = doc.get("wave")
    if wv is not None and (isinstance(wv, bool) or not isinstance(wv, int) or wv < 1):
        problems.append("'wave' must be an int >= 1")
    rd = doc.get("rounds")
    if rd is not None and (isinstance(rd, bool) or not isinstance(rd, int) or rd < 0):
        problems.append("'rounds' must be an int >= 0")
    # coalesced-dispatch probe fields (ISSUE 5): the concurrent-clients
    # speedup is the number the acceptance tracks across rounds, so a
    # malformed one must not be archived
    conc = doc.get("concurrency")
    if conc is not None and (
        isinstance(conc, bool) or not isinstance(conc, int) or conc < 1
    ):
        problems.append("'concurrency' must be an int >= 1")

    def _finite_nonneg(key, minimum=0.0):
        v = doc.get(key)
        if v is None:
            return
        if _bad_finite_nonneg(v, minimum):
            problems.append(f"'{key}' must be null or a finite number >= {minimum:g}")

    _finite_nonneg("coalesce_batch_mean", minimum=1.0)
    _finite_nonneg("p50_score_ms")
    _finite_nonneg("p99_score_ms")
    _finite_nonneg("score_concurrent_speedup")
    # pipelined-dispatch probe fields (ISSUE 6): the vs-coalescer
    # speedup and the device-idle/window health numbers the acceptance
    # tracks — malformed ones must not be archived
    _finite_nonneg("score_pipeline_speedup")
    _finite_nonneg("device_idle_ms")
    _finite_nonneg("coalesce_window_ms")
    lo = doc.get("launch_overlaps")
    if lo is not None and (
        isinstance(lo, bool) or not isinstance(lo, int) or lo < 0
    ):
        problems.append("'launch_overlaps' must be null or an int >= 0")
    ss = doc.get("score_serial_sample")
    if ss is not None and (
        isinstance(ss, bool) or not isinstance(ss, int) or ss < 1
    ):
        problems.append("'score_serial_sample' must be null or an int >= 1")
    # incremental-score-engine probe fields (ISSUE 9): the warm Score
    # cost (dirty-column rescore of the resident [P, N] tensor) vs the
    # full-rescore oracle — the quantity this engine exists to cut, and
    # the one warm-path timing the Assign side had but Score did not
    _finite_nonneg("warm_score_ms")
    _finite_nonneg("full_warm_score_ms")
    _finite_nonneg("incr_score_speedup")
    _finite_nonneg("incr_cols_rescored")
    # fused scoring-term probe fields (ISSUE 15): the fused-vs-
    # per-term-sequential speedup and the term-enabled warm Score cost
    # — the headline numbers of --config plugins, so malformed ones
    # must not be archived
    pt = doc.get("plugin_terms")
    if pt is not None and (
        isinstance(pt, bool) or not isinstance(pt, int) or pt < 1
    ):
        problems.append("'plugin_terms' must be an int >= 1")
    _finite_nonneg("plugin_fused_speedup")
    _finite_nonneg("plugin_fused_ms")
    _finite_nonneg("plugin_oracle_ms")
    _finite_nonneg("plugin_base_ms")
    _finite_nonneg("plugin_warm_score_ms")
    # sparse candidate-set scoring probe fields (ISSUE 16): the [P, C]
    # serving wall at a pods x nodes scale the dense path cannot even
    # allocate — there "OOM" is the legitimate (and expected) dense
    # outcome, but it must be the literal string, never a fabricated
    # number; the speedup comes from a medium scale where both fit
    _finite_nonneg("sparse_score_ms")
    _finite_nonneg("sparse_build_ms")
    dsm = doc.get("dense_score_ms")
    if dsm is not None and dsm != "OOM" and _bad_finite_nonneg(dsm):
        problems.append(
            "'dense_score_ms' must be null, a finite number >= 0, "
            'or the literal "OOM"'
        )
    _finite_nonneg("sparse_speedup")
    cw = doc.get("candidate_width")
    if cw is not None and (
        isinstance(cw, bool) or not isinstance(cw, int) or cw < 1
    ):
        problems.append("'candidate_width' must be an int >= 1")
    crt = doc.get("candidate_refresh_total")
    if crt is not None and (
        isinstance(crt, bool) or not isinstance(crt, int) or crt < 0
    ):
        problems.append("'candidate_refresh_total' must be an int >= 0")
    # mesh-sharded snapshot probe fields (ISSUE 7): the per-shard Sync
    # cost and the mesh-vs-single-chip cycle numbers the acceptance
    # tracks — malformed ones must not be archived
    md = doc.get("mesh_devices")
    if md is not None and (
        isinstance(md, bool) or not isinstance(md, int) or md < 1
    ):
        problems.append("'mesh_devices' must be an int >= 1")
    _finite_nonneg("shard_sync_ms")
    _finite_nonneg("mesh_assign_ms")
    _finite_nonneg("mesh_speedup")
    # replicated-serving-tier probe fields (ISSUE 8): the tier-vs-one-
    # daemon read scaling, the follower lag, and the overload shed rate
    # the acceptance tracks — malformed ones must not be archived
    rc = doc.get("replica_count")
    if rc is not None and (
        isinstance(rc, bool) or not isinstance(rc, int) or rc < 1
    ):
        problems.append("'replica_count' must be an int >= 1")
    _finite_nonneg("replica_lag_ms")
    _finite_nonneg("replica_read_speedup")
    sr = doc.get("shed_rate")
    if sr is not None and (
        isinstance(sr, bool)
        or not isinstance(sr, (int, float))
        or sr != sr
        or not 0.0 <= sr <= 1.0
    ):
        problems.append("'shed_rate' must be null or a number in [0, 1]")
    # relay-tree probe fields (ISSUE 18): the depth-3 converge wall is
    # the headline, and the tree's two claims ride alongside — fan-out
    # amplification (frames the tree moved per frame the root's uplink
    # paid) and the read speedup of storming the leaves instead of one
    # flat follower.  Malformed ones must not be archived.
    td_depth = doc.get("tree_depth")
    if td_depth is not None and (
        isinstance(td_depth, bool) or not isinstance(td_depth, int)
        or td_depth < 1
    ):
        problems.append("'tree_depth' must be an int >= 1")
    _finite_nonneg("tree_fanout_amplification")
    _finite_nonneg("tree_read_speedup")
    _finite_nonneg("frames_per_wakeup")
    ash = doc.get("autoscale_slo_held")
    if ash is not None and not isinstance(ash, bool):
        problems.append("'autoscale_slo_held' must be a boolean")
    # crash-tolerance probe fields (ISSUE 11): leader-SIGKILL recovery
    # economics — both failover legs, the journal replay/append tax,
    # and how many follower full-resyncs the storm cost
    _finite_nonneg("failover_ms")
    _finite_nonneg("warm_restart_ms")
    _finite_nonneg("journal_replay_ms")
    _finite_nonneg("journal_append_us")
    # the warm-restart split (ISSUE 20): journal replay vs jit compile
    # shares of the restart window — the compile share is the quantity
    # the cold-path work attacks, so a fabricated one must not archive
    _finite_nonneg("restart_replay_ms")
    _finite_nonneg("restart_compile_ms")
    # cold-path probe fields (ISSUE 20, --config coldstart): two real
    # subprocess boots (cold vs warm persistent cache + prewarm
    # replay), the prewarm runner's own economics, and the serial-vs-
    # pipelined cold candidate build — malformed ones must not be
    # archived
    _finite_nonneg("cold_start_ms")
    _finite_nonneg("warm_cache_start_ms")
    _finite_nonneg("cold_start_speedup")
    _finite_nonneg("prewarm_ms")
    _finite_nonneg("prewarm_compile_ms")
    _finite_nonneg("cold_build_serial_ms")
    _finite_nonneg("cold_build_ms")
    _finite_nonneg("cold_build_speedup")
    _finite_nonneg("spawn_to_ready_ms")
    for key in ("prewarm_signatures", "prewarm_compiled", "build_nodes"):
        v = doc.get(key)
        if v is not None and (
            isinstance(v, bool) or not isinstance(v, int) or v < 0
        ):
            problems.append(f"'{key}' must be null or an int >= 0")
    # non-negative count fields, one rule: the crash-tolerance probe's
    # (ISSUE 11) and the trace replay's (ISSUE 12) — the latter are
    # the realistic-workload numbers every future round carries
    for key in ("resyncs_during_failover", "reads_during_failover",
                "trace_events", "trace_parity_checks", "trace_retraces",
                "trace_seed", "chaos_trace_events", "chaos_trace_seed",
                "chaos_trace_errors", "chaos_trace_retraces",
                "degraded_replies", "breaker_trips",
                "assembled_traces", "orphan_spans",
                "ancestor_switches", "full_opens_during_failover",
                "compressed_fulls", "autoscale_scale_ups",
                "autoscale_scale_downs", "autoscale_peak_replicas"):
        v = doc.get(key)
        if v is not None and (
            isinstance(v, bool) or not isinstance(v, int) or v < 0
        ):
            problems.append(f"'{key}' must be null or an int >= 0")
    # distributed-tracing overhead field (ISSUE 14): tracing-on vs
    # tracing-off p99 delta in percent — NEGATIVE is legitimate (run
    # noise on a quiet replay), but it must be finite and can never be
    # below -100 (the traced run cannot take negative time)
    top = doc.get("trace_overhead_p99_pct")
    if top is not None and _bad_finite_nonneg(top, minimum=-100.0):
        problems.append(
            "'trace_overhead_p99_pct' must be null or a finite "
            "number >= -100"
        )
    # compile truth fields (ISSUE 19): compile wall paid at the jit
    # boundaries, the dominant kernel's XLA-estimated flops, and the
    # backend the ledger attributed them to; devprof_device_score_us
    # is null since the ledger stopped sampling device time.
    # Malformed ones must not be archived.
    _finite_nonneg("devprof_compile_ms_total")
    _finite_nonneg("devprof_device_score_us")
    _finite_nonneg("devprof_flops_per_launch")
    db = doc.get("devprof_backend")
    if db is not None and (not isinstance(db, str) or not db):
        problems.append(
            "'devprof_backend' must be null or a non-empty string"
        )
    dcomp = doc.get("devprof_compiles")
    if dcomp is not None and (
        isinstance(dcomp, bool) or not isinstance(dcomp, int) or dcomp < 0
    ):
        problems.append("'devprof_compiles' must be null or an int >= 0")
    # sampling-on vs sampling-off p99 delta in percent: negative is
    # legitimate run noise, below -100 is fabricated (same rule as
    # trace_overhead_p99_pct)
    dop = doc.get("devprof_overhead_p99_pct")
    if dop is not None and _bad_finite_nonneg(dop, minimum=-100.0):
        problems.append(
            "'devprof_overhead_p99_pct' must be null or a finite "
            "number >= -100"
        )
    # the parent's TPU probe outcome (WHY a
    # run landed on the CPU leg must ride the artifact, not a log line
    # the driver discards)
    tp = doc.get("tpu_probe")
    if tp is not None and (not isinstance(tp, str) or not tp):
        problems.append("'tpu_probe' must be null or a non-empty string")
    # chaos x trace gate fields (ISSUE 13): the recovery wall, the
    # per-band shed ladder outcome and the combined SLO verdicts —
    # malformed ones must not be archived
    _finite_nonneg("recovery_ms")
    sbb = doc.get("shed_by_band")
    if sbb is not None:
        if not isinstance(sbb, dict):
            problems.append("'shed_by_band' must be an object")
        else:
            for name, v in sbb.items():
                if not isinstance(name, str) or not name:
                    problems.append(
                        "'shed_by_band' keys must be non-empty strings"
                    )
                elif isinstance(v, bool) or not isinstance(v, int) or v < 0:
                    problems.append(
                        f"'shed_by_band.{name}' must be an int >= 0"
                    )
    # trace-replay SLO-gate fields (ISSUE 12): per-band / per-RPC
    # p99s and the declarative SLO verdicts; malformed ones must not
    # be archived
    for key in ("trace_digest", "chaos_trace_digest"):
        td = doc.get(key)
        if td is not None and (not isinstance(td, str) or not td):
            problems.append(f"'{key}' must be a non-empty string")
    for key in ("trace_slo_pass", "chaos_trace_slo_pass"):
        tsp = doc.get(key)
        if tsp is not None and not isinstance(tsp, bool):
            problems.append(f"'{key}' must be a boolean")
    for key in ("trace_band_p99_ms", "trace_rpc_p99_ms",
                "storm_band_p99_ms"):
        obj = doc.get(key)
        if obj is None:
            continue
        if not isinstance(obj, dict):
            problems.append(f"'{key}' must be an object")
            continue
        for name, v in obj.items():
            if not isinstance(name, str) or not name:
                problems.append(f"'{key}' keys must be non-empty strings")
            elif v is not None and _bad_finite_nonneg(v):
                problems.append(
                    f"'{key}.{name}' must be null or a finite number >= 0"
                )
    def _check_slo_list(key):
        """One SLO-verdict-list field (trace_slo / chaos_trace_slo):
        the obs/slo.py SloVerdict.to_doc shape."""
        slo = doc.get(key)
        if slo is None:
            return
        if not isinstance(slo, list):
            problems.append(f"'{key}' must be a list")
            return
        for i, verdict in enumerate(slo):
            if not isinstance(verdict, dict):
                problems.append(f"'{key}[{i}]' must be an object")
                continue
            if not isinstance(verdict.get("name"), str) or not verdict.get("name"):
                problems.append(
                    f"'{key}[{i}].name' must be a non-empty string"
                )
            if not isinstance(verdict.get("ok"), bool):
                problems.append(f"'{key}[{i}].ok' must be a boolean")
            q = verdict.get("quantile")
            if (
                isinstance(q, bool)
                or not isinstance(q, (int, float))
                or not 0.0 < q <= 1.0
            ):
                problems.append(
                    f"'{key}[{i}].quantile' must be in (0, 1]"
                )
            for field in ("threshold_ms", "observed_ms"):
                v = verdict.get(field)
                if field == "observed_ms" and v is None:
                    continue  # no-data verdicts observe nothing
                if _bad_finite_nonneg(v):
                    problems.append(
                        f"'{key}[{i}].{field}' must be a finite "
                        "number >= 0"
                    )

    _check_slo_list("trace_slo")
    _check_slo_list("chaos_trace_slo")
    # per-stage span summary (ISSUE 4): stage name -> milliseconds, or
    # null for a stage that measured nothing (a failed best-effort leg
    # must stay VISIBLE as null, never invented) — so BENCH_*.json
    # trajectories carry stage breakdowns, not just headline numbers
    spans = doc.get("spans")
    if spans is not None:
        if not isinstance(spans, dict):
            problems.append("'spans' must be an object")
        else:
            for name, v in spans.items():
                if not isinstance(name, str) or not name:
                    problems.append("'spans' keys must be non-empty strings")
                elif v is not None and _bad_finite_nonneg(v):
                    problems.append(
                        f"'spans.{name}' must be null or a finite "
                        "number >= 0"
                    )
    return problems


_DEADLINE: Optional["_ArtifactDeadline"] = None


def _emit_artifact(line: Optional[str]) -> bool:
    """Validate-then-print gate for every artifact line; schema failures
    go to stderr and the caller exits non-zero instead of publishing."""
    problems = _validate_artifact(line)
    if problems:
        print(
            f"malformed bench artifact suppressed: {'; '.join(problems)}; "
            f"line was: {line!r:.300}",
            file=sys.stderr,
        )
        return False
    # one artifact per run: claim the deadline's once-flag BEFORE
    # printing — a SIGTERM landing between the print and a
    # cancel-afterwards would emit a second, "truncated" line behind a
    # successful one.  (fire() itself holds the flag already, so its
    # own emit is unaffected.)
    if _DEADLINE is not None:
        _DEADLINE.cancel()
    print(line, flush=True)
    return True


def _quota_snapshot(encode_snapshot, generators, res, build_quota_table_inputs):
    """The headline 10k x 2k quota_colocation snapshot — the ONE recipe
    (harness.generators.quota_colocation_snapshot) shared by the headline
    child, the extras/rebalance configs, the multichip dryrun, and the
    parity tests, so every number in BASELINE.md measures the same
    cluster.  (The module args are kept for call-site stability; the
    recipe lives in the harness now.)"""
    del encode_snapshot, res, build_quota_table_inputs
    return generators.quota_colocation_snapshot(pods=PODS, nodes=NODES)


def child(platform: str) -> None:
    """Measurement process: prints phase lines then the final JSON line."""

    def phase(name, **kw):
        print(json.dumps({"phase": name, **kw}), flush=True)

    # per-stage span summary for the artifact (ISSUE 4): every key is
    # pre-seeded so a stage that measured nothing publishes null — the
    # schema (_validate_artifact) accepts exactly that shape
    spans = {
        "init": None, "rtt_floor": None, "snapshot": None,
        "lowering_probe": None, "compile": None, "steady": None,
        "incr_score": None,
        "cpu_native": None, "cpu_native_mt": None, "devprof": None,
    }

    t0 = time.perf_counter()
    import jax  # noqa: E402  (may hang; parent enforces the timeout)

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    n_dev = len(jax.devices())
    spans["init"] = round(_ms(t0), 2)
    phase("init", backend=backend, devices=n_dev, ms=spans["init"])

    # fixed dispatch+transfer floor of the platform — reported so
    # device-kernel time can be read net of transport
    import numpy as np

    _trivial = jax.jit(lambda x: x + 1)
    _x = jax.numpy.zeros(8)
    np.asarray(_trivial(_x))
    rtt_ms = min(
        _timed(lambda: np.asarray(_trivial(_x))) for _ in range(5)
    )
    spans["rtt_floor"] = round(rtt_ms, 2)
    phase("rtt_floor", ms=round(rtt_ms, 2))

    t0 = time.perf_counter()
    import koordinator_tpu  # noqa: F401  (enables x64)
    from koordinator_tpu.constraints import build_quota_table_inputs
    from koordinator_tpu.harness import generators
    from koordinator_tpu.model import encode_snapshot, resources as res
    from koordinator_tpu.solver import pallas_inputs_fit_i32

    snap, nodes, pods, gangs, quotas, qdicts = _quota_snapshot(
        encode_snapshot, generators, res, build_quota_table_inputs
    )
    spans["snapshot"] = round(_ms(t0), 2)
    phase("snapshot", ms=spans["snapshot"])

    on_tpu = backend != "cpu"
    if on_tpu:
        # the flagship single-kernel cycle (dense layout: nodes on lanes,
        # solver/pallas_dense.py) — invoked directly, so a compile or
        # runtime failure is a bench FAILURE, never a silent scan
        assert pallas_inputs_fit_i32(snap), "bench snapshot out of i32 range"
        from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

        # tiny-shape Mosaic lowering probe first: a kernel that fails to
        # lower errors HERE in seconds with the Mosaic message in stderr,
        # distinguishable from a hang at the big compile
        t0 = time.perf_counter()
        small = encode_snapshot(
            nodes[:16], pods[:64], [], qdicts, node_bucket=16, pod_bucket=64
        )
        r = greedy_assign_dense(small)
        np.asarray(r.assignment)
        spans["lowering_probe"] = round(_ms(t0), 2)
        phase("pallas_lowering_probe", ms=spans["lowering_probe"], path=r.path)

        run = lambda: greedy_assign_dense(snap)
        path = "pallas"
    else:
        from koordinator_tpu.solver import greedy_assign

        run = lambda: greedy_assign(snap)
        path = "scan"

    # compile + first execution.  NOTE: timing forces a host transfer of
    # the result, so the time covers execution, not the enqueue.  The
    # assignment vector is 40 KB, so the transfer cost itself is
    # negligible.
    t0 = time.perf_counter()
    result = run()
    np.asarray(result.assignment)
    compile_ms = _ms(t0)
    spans["compile"] = round(compile_ms, 2)
    phase("compile", ms=compile_ms, path=path)

    times = []
    for _ in range(6):
        t0 = time.perf_counter()
        result = run()
        np.asarray(result.assignment)
        times.append(_ms(t0))
    # min over 6 reps: the min tracks the device+transport floor
    ms = min(times)
    spans["steady"] = round(ms, 2)
    assigned = int((np.asarray(result.assignment)[:PODS] >= 0).sum())
    assert assigned > 0, "benchmark snapshot scheduled nothing"
    assert result.path == path, f"expected {path} path, ran {result.path}"

    # incremental score engine (ISSUE 9) at headline scale: the warm
    # Score cost through the dirty-column rescore vs the full-rescore
    # oracle — same probe implementation as --config bridge (the parity
    # assert rides inside it).  Best-effort: a failure publishes nulls.
    warm_score_ms = full_warm_score_ms = None
    incr_score_speedup = incr_cols_rescored = None
    try:
        from koordinator_tpu.harness.golden import build_sync_request

        sync_req, _ = build_sync_request(nodes, pods, gangs, quotas)
        (warm_score_ms, full_warm_score_ms,
         incr_score_speedup, incr_cols_rescored) = (
            _incr_score_probe(sync_req.SerializeToString())
        )
        del sync_req
        spans["incr_score"] = round(warm_score_ms, 2)
        phase(
            "incr_score",
            warm_score_ms=round(warm_score_ms, 2),
            full_warm_score_ms=round(full_warm_score_ms, 2),
            speedup=round(incr_score_speedup, 3),
            cols=round(incr_cols_rescored, 1),
        )
    except Exception as exc:  # noqa: BLE001
        phase("incr_score_failed", error=str(exc)[:200])

    # measured native CPU baseline (BASELINE.md): the sequential per-pod
    # C++ cycle (native/score_baseline.cpp) on the same snapshot — the
    # shape of the reference's Go Score hot loop, Go toolchain absent.
    # Runs AFTER the device measurement so it can never starve the TPU
    # compile of its timeout budget, and only in the child that already
    # succeeded (failed attempts never reach it).  Best-effort: a baseline
    # failure must never kill the bench artifact.
    import tempfile

    cpu_native_ms = None
    cpu_native_mt_ms = None
    hw_threads = None
    with tempfile.TemporaryDirectory() as tmp:
        binary = golden = None
        try:
            binary, golden = _native_prepare(nodes, pods, gangs, quotas, tmp)
            cpu_native_ms, _, _ = _native_run(binary, golden)
            spans["cpu_native"] = cpu_native_ms
            phase("cpu_native_baseline", ms=cpu_native_ms)
        except Exception as exc:  # noqa: BLE001
            phase("cpu_native_baseline_failed", error=str(exc)[:200])
        try:
            # the 16-way node-loop fan-out (the reference's Parallelizer
            # width) on the same golden.  On a host with < 16 cores this
            # measures honest oversubscription, not speedup —
            # hw_concurrency is recorded so the reader can tell;
            # BASELINE.md carries the extrapolation.
            if binary is not None:
                cpu_native_mt_ms, _, mt_info = _native_run(
                    binary, golden, iters=2, threads=16
                )
                hw_threads = mt_info.get("hw_concurrency")
                spans["cpu_native_mt"] = cpu_native_mt_ms
                phase(
                    "cpu_native_mt",
                    ms=cpu_native_mt_ms,
                    hw_concurrency=hw_threads,
                )
            else:
                phase("cpu_native_mt_failed", error="baseline prepare failed")
        except Exception as exc:  # noqa: BLE001
            phase("cpu_native_mt_failed", error=str(exc)[:200])

    # compile truth (ISSUE 19): a short ledger-on leg at probe scale.
    # The headline timings above ran with the ledger OFF (its default)
    # so they stay comparable across rounds; this leg pays its own AOT
    # captures on a small snapshot and publishes the compile costs the
    # daemon's /metrics families carry.  The ledger samples no device
    # time (a profiler trace does), so device_score_us stays null.
    # Best-effort: a devprof failure publishes nulls, never kills the
    # artifact.
    devprof_backend = devprof_compiles = None
    devprof_compile_ms_total = None
    devprof_device_score_us = devprof_flops_per_launch = None
    try:
        from koordinator_tpu.obs import devprof
        from koordinator_tpu.solver import greedy_assign as _dp_assign
        from koordinator_tpu.solver.greedy import score_cycle as _dp_score

        t0 = time.perf_counter()
        devprof.reset()
        devprof.configure(attribute=True)
        dsnap = encode_snapshot(
            nodes[:16], pods[:64], [], qdicts, node_bucket=16, pod_bucket=64
        )
        np.asarray(_dp_score(dsnap)[0])  # cold: AOT compile capture
        np.asarray(_dp_assign(dsnap).assignment)
        for _ in range(4):  # warm: no further capture
            np.asarray(_dp_score(dsnap)[0])
        summ = devprof.summary()
        devprof_backend = summ["backend"]
        ents = [
            e for e in summ["entries"] if e["compile_ms"] is not None
        ]
        devprof_compiles = len(ents)
        devprof_compile_ms_total = sum(e["compile_ms"] for e in ents)
        flops = [e["flops"] for e in summ["entries"] if e.get("flops")]
        if flops:
            devprof_flops_per_launch = max(flops)
        spans["devprof"] = round(_ms(t0), 2)
        phase(
            "devprof",
            backend=devprof_backend,
            compiles=devprof_compiles,
            compile_ms_total=round(devprof_compile_ms_total, 2),
            device_score_us=(
                round(devprof_device_score_us, 1)
                if devprof_device_score_us is not None else None
            ),
            flops_per_launch=devprof_flops_per_launch,
        )
    except Exception as exc:  # noqa: BLE001
        phase("devprof_failed", error=str(exc)[:200])
    finally:
        # the ledger is process-global: back to bit-inert before
        # anything else in this child touches the serving path
        try:
            from koordinator_tpu.obs import devprof
            devprof.configure(attribute=False)
            devprof.reset()
        except Exception:  # koordlint: disable=broad-except(reason: best-effort ledger teardown in the finally arm — the probe already published or phase()d its failure, and the artifact must still print)
            pass
    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": round(ms, 2),
                "unit": "ms",
                "vs_baseline": round(TARGET_MS / ms, 3),
                "backend": backend,
                "path": result.path,
                "compile_ms": round(compile_ms, 1),
                "assigned": assigned,
                # measured single-thread C++ sequential baseline on this
                # host (None if the native build was unavailable)
                "cpu_native_baseline_ms": cpu_native_ms,
                "vs_cpu_native": (
                    round(cpu_native_ms / ms, 3) if cpu_native_ms else None
                ),
                # 16-thread node-loop fan-out on this host (honest only
                # when cpu_hw_concurrency >= 16; see BASELINE.md)
                "cpu_native_mt_ms": cpu_native_mt_ms,
                "vs_cpu_native_mt": (
                    round(cpu_native_mt_ms / ms, 3)
                    if cpu_native_mt_ms
                    else None
                ),
                "cpu_hw_concurrency": hw_threads,
                # per-call transport floor of this platform; subtract for
                # net device-kernel time
                "rtt_floor_ms": round(rtt_ms, 2),
                # incremental score engine (ISSUE 9): warm Score via
                # dirty-column rescore vs full-rescore oracle, <=64
                # dirty nodes; null = the probe failed / did not run
                "warm_score_ms": (
                    round(warm_score_ms, 2)
                    if warm_score_ms is not None else None
                ),
                "incr_score_speedup": (
                    round(incr_score_speedup, 3)
                    if incr_score_speedup is not None else None
                ),
                "incr_cols_rescored": (
                    round(incr_cols_rescored, 1)
                    if incr_cols_rescored is not None else None
                ),
                # device-time truth (ISSUE 19): the ledger-on probe
                # leg's compile-vs-device split at small scale; null =
                # the leg failed / did not run
                "devprof_backend": devprof_backend,
                "devprof_compiles": devprof_compiles,
                "devprof_compile_ms_total": (
                    round(devprof_compile_ms_total, 2)
                    if devprof_compile_ms_total is not None else None
                ),
                "devprof_device_score_us": (
                    round(devprof_device_score_us, 1)
                    if devprof_device_score_us is not None else None
                ),
                "devprof_flops_per_launch": devprof_flops_per_launch,
                # per-stage breakdown (ISSUE 4): null = the stage
                # measured nothing (failed best-effort leg, or a stage
                # this platform never runs)
                "spans": spans,
            }
        ),
        flush=True,
    )


def _native_prepare(nodes, pods, gangs, quotas, tmpdir):
    """Build the baseline binary once and serialize one golden snapshot;
    returns (binary_path, golden_path) for any number of _native_run calls."""
    from koordinator_tpu.harness.golden import write_golden

    here = os.path.dirname(os.path.abspath(__file__))
    native_dir = os.path.join(here, "native")
    subprocess.run(
        ["make", "-C", native_dir, "score_baseline"],
        capture_output=True,
        timeout=120,
        check=True,
    )
    golden = os.path.join(tmpdir, "golden.bin")
    write_golden(golden, nodes, pods, gangs, quotas)
    return os.path.join(native_dir, "score_baseline"), golden


def _native_run(binary, golden, iters=3, threads=1):
    """Run the C++ baseline (sequential per-pod cycle; node loop fanned out
    over ``threads`` OpenMP threads when > 1, the reference's Parallelizer
    shape at framework_extender.go:216) on a prepared golden snapshot.

    Returns (ms, native_assignment list, info dict with threads and the
    host's hw_concurrency).  Raises on any failure — callers decide
    whether that is fatal (parity checks) or best-effort (metrics)."""
    out = subprocess.run(
        [binary, golden, str(iters), str(threads)],
        capture_output=True,
        text=True,
        timeout=300,
        check=True,
    )
    lines = out.stdout.splitlines()
    info = json.loads(lines[0])
    assign = [int(v) for v in lines[1].split()[1:]]
    return info["value"], assign, info


def _native_baseline(nodes, pods, gangs, quotas, iters=3, threads=1):
    """One-shot prepare + run (single-measurement call sites)."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        binary, golden = _native_prepare(nodes, pods, gangs, quotas, tmp)
        return _native_run(binary, golden, iters, threads)


def _recv_exact(conn, n: int) -> bytes:
    """Raising wrapper over the bridge transport's frame reader (one
    framing implementation: bridge/udsserver.py)."""
    from koordinator_tpu.bridge import udsserver

    out = udsserver._recv_exact(conn, n)
    if out is None:
        raise ConnectionError("socket closed mid-frame")
    return out


def _score_storm(sock_path, snapshot_id, clients=8, per_client=3, top_k=32,
                 on_start=None):
    """Concurrent-clients Score probe (ISSUE 5): ``clients`` raw-UDS
    connections each fire ``per_client`` flat top-k Scores at once
    (after one untimed warm-up each, so neither compile nor connect
    cost pollutes the comparison).  Returns ``(wall_s, sorted
    per-request latencies ms, reply digest set, errors)`` — the digest
    set proves the demultiplexed coalesced replies are byte-identical
    to the serialized server's for the same snapshot.

    The replica tier's M x N storms (ISSUE 8) do NOT drive this with a
    socket list from one process — a single bench-process GIL would
    pace the arrivals; ``--replica-storm`` runs one of these per
    replica instead (``replica_storm``)."""
    import hashlib
    import socket
    import struct

    from koordinator_tpu.bridge.codegen import pb2
    from koordinator_tpu.bridge.udsserver import METHOD_SCORE

    body = pb2.ScoreRequest(
        snapshot_id=snapshot_id, top_k=top_k, flat=True
    ).SerializeToString()
    lats, digests, errors = [], set(), []
    lock = threading.Lock()
    # +1 on both barriers: the main thread snapshots baseline stats
    # (on_start) and starts the wall clock BETWEEN them — after every
    # warm-up completed, strictly before any timed request can run
    warmed = threading.Barrier(clients + 1)
    released = threading.Barrier(clients + 1)

    def worker():
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(sock_path)

            def call():
                conn.sendall(
                    struct.pack(">BI", METHOD_SCORE, len(body)) + body
                )
                status, ln = struct.unpack(">BI", _recv_exact(conn, 5))
                out = _recv_exact(conn, ln)
                assert status == 0, out
                return out

            call()  # warm-up: compile + cold snapshot build, untimed
            warmed.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
            released.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
            for _ in range(per_client):
                t0 = time.perf_counter()
                out = call()
                ms = _ms(t0)
                flat = pb2.ScoreReply.FromString(out).flat
                digest = hashlib.sha256(
                    flat.pod_index + flat.counts + flat.node_index
                    + flat.score
                ).hexdigest()
                with lock:
                    lats.append(ms)
                    digests.add(digest)
            conn.close()
        except Exception as exc:  # noqa: BLE001  (collected, asserted by caller)
            with lock:
                errors.append(repr(exc))
            for b in (warmed, released):
                try:
                    b.abort()
                except threading.BrokenBarrierError:
                    pass  # already broken by another failed worker

    threads = [
        threading.Thread(target=worker, daemon=True) for _ in range(clients)
    ]
    for t in threads:
        t.start()
    try:
        warmed.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
        if on_start is not None:
            # snapshot dispatcher stats AFTER the untimed warm-ups and
            # BEFORE any worker is released, so batch-occupancy means
            # measure only the storm itself (no race with the first
            # timed request)
            on_start()
        t0 = time.perf_counter()
        released.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
    except threading.BrokenBarrierError:
        t0 = time.perf_counter()  # a worker failed; error is collected
    for t in threads:
        t.join(timeout=600)
    wall_s = time.perf_counter() - t0
    return wall_s, sorted(lats), digests, errors


def _shed_storm(sock_path, snapshot_id, clients=32, top_k=32):
    """Overload burst against an admission-gated daemon (ISSUE 8): each
    worker fires exactly ONE flat top-k Score from behind a barrier, so
    the gate sees the whole burst at once.  Returns ``(served digest
    set, shed count, other error list, max shed-reply latency ms)`` —
    served replies prove in-flight work completed untouched, the shed
    latency proves rejections are fast (bounded), never queued."""
    import hashlib
    import socket
    import struct

    from koordinator_tpu.bridge.codegen import pb2
    from koordinator_tpu.bridge.udsserver import METHOD_SCORE

    body = pb2.ScoreRequest(
        snapshot_id=snapshot_id, top_k=top_k, flat=True
    ).SerializeToString()
    digests, errors = set(), []
    shed = 0
    shed_ms = []
    lock = threading.Lock()
    released = threading.Barrier(clients + 1)

    def worker():
        nonlocal shed
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(sock_path)
            released.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
            t0 = time.perf_counter()
            conn.sendall(struct.pack(">BI", METHOD_SCORE, len(body)) + body)
            status, ln = struct.unpack(">BI", _recv_exact(conn, 5))
            out = _recv_exact(conn, ln)
            ms = _ms(t0)
            conn.close()
            if status == 0:
                flat = pb2.ScoreReply.FromString(out).flat
                digest = hashlib.sha256(
                    flat.pod_index + flat.counts + flat.node_index
                    + flat.score
                ).hexdigest()
                with lock:
                    digests.add(digest)
            elif b"RESOURCE_EXHAUSTED" in out:
                with lock:
                    shed += 1
                    shed_ms.append(ms)
            else:
                with lock:
                    errors.append(out[:200])
        except Exception as exc:  # noqa: BLE001  (collected, asserted by caller)
            with lock:
                errors.append(repr(exc))
            try:
                released.abort()
            except threading.BrokenBarrierError:
                pass

    threads = [
        threading.Thread(target=worker, daemon=True) for _ in range(clients)
    ]
    for t in threads:
        t.start()
    try:
        released.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
    except threading.BrokenBarrierError:
        pass
    for t in threads:
        t.join(timeout=600)
    return digests, shed, errors, (max(shed_ms) if shed_ms else 0.0)


def _incr_score_probe(sync_payload, reps=3, dirty_nodes=64, top_k=32,
                      cfg=None, guard=False):
    """ISSUE 9 probe: warm Score through the incremental engine vs the
    full-rescore oracle — the ONE implementation behind both the bridge
    and headline artifacts' ``warm_score_ms`` / ``incr_score_speedup``
    / ``incr_cols_rescored`` fields.

    Two in-process servicers replay the same stream (full Sync, one
    untimed warm-up delta+Score per engine to compile the warm paths,
    then ``reps`` x a <=``dirty_nodes``-row delta Sync followed by a
    flat top-k Score), with the reply payload bytes asserted identical
    per rep — the speedup is only publishable against a digest-equal
    oracle.  The saving is arithmetic (O(P x d) vs O(P x N) rescoring,
    both sides paying the same masked top_k), so unlike the mesh and
    pipeline probes it is host-visible on CPU.

    Returns (warm_score_ms, full_warm_score_ms, speedup, cols_mean).

    ``cfg``: CycleConfig for both servicers — the ``--config plugins``
    child passes a three-term config (ISSUE 15) so the warm stream is
    measured with every fused term enabled.  ``guard=True`` arms
    ``retrace_guard(budget=0)`` around the measured reps (after the
    internal warm-up), asserting the term-enabled warm path stays
    retrace-free.
    """
    import numpy as np

    from koordinator_tpu.bridge.codegen import pb2
    from koordinator_tpu.bridge.server import ScorerServicer
    from koordinator_tpu.bridge.state import numpy_to_tensor

    sv_kw = {} if cfg is None else {"cfg": cfg}
    incr_sv = ScorerServicer(score_memo=False, **sv_kw)
    full_sv = ScorerServicer(score_memo=False, score_incr=False, **sv_kw)
    for sv in (incr_sv, full_sv):
        sv.sync(pb2.SyncRequest.FromString(sync_payload))

    def score(sv):
        t0 = time.perf_counter()
        reply = sv.score(pb2.ScoreRequest(
            snapshot_id=sv.snapshot_id(), top_k=top_k, flat=True
        ))
        return reply.flat.SerializeToString(), _ms(t0)

    # spread the dirty rows across the table (a delta touching one
    # contiguous corner would understate gather/scatter cost), and cap
    # them at an eighth of it so a scaled-down run stays under the
    # engine's default 0.25 dirty-ratio gate (at the real 10k x 2k
    # scale the cap is inert: 64 of 2000 nodes)
    base = np.asarray(incr_sv.state.node_requested, np.int64).copy()
    n_real = base.shape[0]
    dirty_nodes = min(int(dirty_nodes), max(1, n_real // 8))
    rows = np.unique(
        (np.arange(dirty_nodes) * max(1, n_real // dirty_nodes)) % n_real
    )

    def delta(rep):
        prev = base.copy()
        base[rows, 0] += 1 + rep
        warm = pb2.SyncRequest()
        warm.nodes.requested.CopyFrom(numpy_to_tensor(base, prev))
        raw = warm.SerializeToString()
        for sv in (incr_sv, full_sv):
            sv.sync(pb2.SyncRequest.FromString(raw))
            assert sv.state.last_sync_path == "warm", (
                "probe delta must land on the resident tensors"
            )

    # warm-up: the cold Score populates the residency and compiles the
    # full path; one delta+Score compiles the dirty-bucket rescore
    score(incr_sv)
    score(full_sv)
    delta(0)
    score(incr_sv)
    score(full_sv)
    import contextlib

    from koordinator_tpu.analysis import retrace_guard

    guard_cm = (
        retrace_guard(budget=0) if guard else contextlib.nullcontext()
    )
    incr_times, full_times = [], []
    with guard_cm:
        for rep in range(1, reps + 1):
            delta(rep)
            d_incr, t_incr = score(incr_sv)
            d_full, t_full = score(full_sv)
            assert d_incr == d_full, (
                "incremental Score diverged from the full-rescore oracle"
            )
            incr_times.append(t_incr)
            full_times.append(t_full)
    reg = incr_sv.telemetry.registry
    launched = reg.get(
        "koord_scorer_score_incr_total", {"result": "incr"}
    ) or 0
    assert launched >= reps, (
        f"probe Scores fell back instead of rescoring incrementally "
        f"({launched} incr launches)"
    )
    count, total = reg.get_histogram("koord_scorer_incr_cols", {})
    cols_mean = (total / count) if count else 0.0
    warm_ms, full_ms = min(incr_times), min(full_times)
    return warm_ms, full_ms, full_ms / warm_ms, cols_mean


def _extrapolate_serial(wall_s: float, measured: int, total: int) -> float:
    """Scale a sampled serialized-baseline storm wall to the full
    request count.  Valid ONLY for the max_batch=1/depth=1 engine:
    it admits exactly one request into the device section at a time,
    so storm wall is the sum of per-request service times and grows
    linearly in the number of requests, independent of client fan-in.
    ``measured`` <= 0 or >= ``total`` returns the wall unchanged."""
    if measured <= 0 or measured >= total:
        return wall_s
    return wall_s * (total / measured)


def _ms(t0: float) -> float:
    return (time.perf_counter() - t0) * 1000.0


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return _ms(t0)


def _one_process_rule(backend: str, config: str) -> None:
    """A chip belongs to one process: this bench child already holds
    the backend, so a config that spawns chip-bound daemon processes
    would leave them failing on the libtpu lock or hanging.  Off the
    CPU such configs exit at once with this error."""
    if backend != "cpu":
        raise SystemExit(
            f"--config {config} spawns daemon processes that need the "
            f"{backend} device this process already holds; one process "
            "per chip — run it with --platform cpu"
        )


def child_config(platform: str, config: str) -> None:
    """Per-config measurement (BASELINE.md's remaining targets): spark
    3-node exact-score parity, LoadAware joint 1k x 200, gang 5k x 500,
    the composed extended-plugin cycle (extras), and the LowNodeLoad
    rebalance — the last three on the 10k x 2k snapshot.  Prints one
    JSON line."""

    def phase(name, **kw):
        print(json.dumps({"phase": name, **kw}), flush=True)

    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    phase("init", backend=backend)

    import numpy as np

    import koordinator_tpu  # noqa: F401
    from koordinator_tpu.harness import generators
    from koordinator_tpu.model import encode_snapshot, resources as res
    from koordinator_tpu.model.snapshot import PriorityClass, estimate_pod

    def _est(p):
        return estimate_pod(
            res.resource_vector(p["requests"]),
            res.resource_vector(p.get("limits", {})),
            PriorityClass.from_name(p.get("priority_class"))
            if p.get("priority_class") is not None
            else PriorityClass.from_priority_value(p.get("priority")),
        )

    if config == "trace":
        # ISSUE 12: trace-driven cluster simulator + continuous SLO
        # gate.  A seeded multi-band event stream (gang arrivals
        # respecting minMember, ElasticQuota pressure waves, node
        # drains/resizes, per-band priority churn) replays through the
        # full client -> UDS gRPC -> coalescer -> device path on BOTH
        # the full-engine servicer and the serialized oracle: reply
        # digests compared per event, the measured pass held at zero
        # jit cache misses (retrace_guard raises otherwise), and the
        # per-band p99s judged by the declarative obs/slo.py specs —
        # the artifact carries the verdicts, so every future round has
        # a realistic-workload number beside the microbenchmark.
        from koordinator_tpu.harness.trace import (
            TraceConfig,
            TraceReplay,
            default_slo_specs,
            generate_trace,
        )
        from koordinator_tpu.obs import validate_flight_dump
        from koordinator_tpu.obs import slo as slo_mod

        def _env_int(name, default):
            # `or`: empty value means unset (the KOORD_* convention)
            return int(os.environ.get(name) or default)

        on_cpu = backend == "cpu"
        # the gang region and tenant count scale WITH the pod-slot
        # knob (floored at 16 slots): pinning them while pod_slots is
        # operator-sizable would make small KOORD_BENCH_TRACE_PODS
        # values crash generate_trace's gang-region check instead of
        # producing a smaller trace
        pod_slots = max(16, _env_int(
            "KOORD_BENCH_TRACE_PODS", 256 if on_cpu else 2048
        ))
        gang_min_member = 4
        gangs = max(1, min(12, pod_slots // (4 * gang_min_member)))
        tcfg = TraceConfig(
            seed=_env_int("KOORD_BENCH_TRACE_SEED", 0),
            nodes=_env_int(
                "KOORD_BENCH_TRACE_NODES", 64 if on_cpu else 512
            ),
            pod_slots=pod_slots,
            tenants=max(2, min(8, pod_slots // 32)),
            gangs=gangs,
            gang_min_member=gang_min_member,
            events=max(1, _env_int(
                "KOORD_BENCH_TRACE_EVENTS", 48 if on_cpu else 96
            )),
        )
        trace = generate_trace(tcfg)
        phase(
            "trace_generated",
            events=len(trace.events),
            digest=trace.digest()[:12],
            bands=trace.bands(),
        )
        # run() = one untimed warm-up pass over the identical stream,
        # then the measured pass under retrace_guard(budget=0): a warm
        # event that retraces, or a reply byte diverging from the
        # serial oracle, raises here — no artifact is published on a
        # broken invariant
        report = TraceReplay(trace).run()
        phase(
            "trace_replayed",
            wall_ms=round(report.wall_ms, 1),
            parity_checks=report.parity_checks,
            retraces=report.retraces,
        )
        timeline = report.timeline_document()
        problems = validate_flight_dump(timeline)
        assert not problems, (
            f"trace timeline failed the flight-dump schema: {problems}"
        )
        specs = default_slo_specs(trace.bands())
        verdicts = slo_mod.evaluate_slos(report.registry, specs)
        band_p99 = {
            band: report.quantile(0.99, band=band)
            for band in trace.bands()
        }
        rpc_p99 = {
            rpc: report.quantile(0.99, rpc=rpc)
            for rpc in ("sync", "score", "assign", "cycle")
        }
        overall_p99 = report.quantile(0.99)
        # a replay with zero recorded steps (a pathological mix where
        # the generator could act on nothing) has no latency to
        # publish — fail the stage honestly instead of crashing on
        # round(None) below; the parent's error artifact says why
        assert overall_p99 is not None, (
            "trace replay recorded no latency observations "
            f"({report.events_replayed} events replayed)"
        )
        # distributed-tracing overhead (ISSUE 14): replay the SAME
        # stream with span export on (client + servicer), measure the
        # p99 delta against the untraced run above, and assemble the
        # export directory — 100% of the replayed RPCs must come back
        # as complete trees with zero orphans, or the artifact is not
        # published (a tracing layer that loses spans measured nothing)
        import tempfile

        from koordinator_tpu.obs import assemble as assemble_mod

        def _raw_cycle_p99(rep):
            # RAW per-event latencies from the replay timeline, not the
            # bucket-quantile estimate: at bench scale the histogram
            # buckets are coarse enough that one sample crossing a
            # boundary reads as a 2x "regression" — the overhead delta
            # needs exact percentiles, the SLO gate keeps its
            # Prometheus-semantics estimator
            lat = [c["notes"]["latency_ms"] for c in rep.timeline]
            assert lat, "replay timeline is empty"
            return float(np.percentile(np.asarray(lat, float), 99))

        # interleaved min-of-k: back-to-back replays on this shared
        # container swing 2x run to run (scheduler noise), so a single
        # off/on pair cannot resolve a 5% delta — alternate the modes
        # and take each mode's BEST p99 (the run least perturbed by
        # the machine), the standard noise-robust estimator.  Repeat
        # passes skip the warm-up (the process jit cache already holds
        # every shape the first run compiled).
        reps = max(1, int(
            os.environ.get("KOORD_TRACE_OVERHEAD_REPS") or "3"
        ))
        p99_off_runs = [_raw_cycle_p99(report)]
        p99_on_runs = []
        with tempfile.TemporaryDirectory(
            prefix="koord-bench-traces-"
        ) as trace_td:
            for rep_i in range(reps):
                traced_report = TraceReplay(
                    trace, trace_export=trace_td, warmup=False
                ).run()
                p99_on_runs.append(_raw_cycle_p99(traced_report))
                if rep_i + 1 < reps:
                    p99_off_runs.append(_raw_cycle_p99(
                        TraceReplay(trace, warmup=False).run()
                    ))
            assembly = assemble_mod.assemble([trace_td])
            assembled_traces = len(assembly.traces)
            orphan_spans = len(assembly.orphan_spans)
            incomplete = len(assembly.incomplete)
        p99_off = min(p99_off_runs)
        p99_on = min(p99_on_runs)
        overhead_pct = (p99_on - p99_off) / p99_off * 100.0
        phase(
            "trace_overhead",
            p99_off_ms=round(p99_off, 3),
            p99_on_ms=round(p99_on, 3),
            overhead_pct=round(overhead_pct, 2),
            assembled_traces=assembled_traces,
            orphan_spans=orphan_spans,
            incomplete_traces=incomplete,
        )
        assert assembled_traces > 0, "tracing-on replay exported no traces"
        assert orphan_spans == 0 and incomplete == 0, (
            f"{orphan_spans} orphan span(s), {incomplete} incomplete "
            "trace(s) after assembling the traced replay's exports"
        )
        # the acceptance bound (≤5% by default); overridable for noisy
        # shared hosts (`or`: empty env value means unset)
        max_overhead_pct = float(
            os.environ.get("KOORD_TRACE_OVERHEAD_MAX_PCT") or "5.0"
        )
        assert overhead_pct <= max_overhead_pct, (
            f"tracing overhead {overhead_pct:.2f}% exceeds the "
            f"{max_overhead_pct:.1f}% bound (raw cycle p99 "
            f"{p99_off:.3f} -> {p99_on:.3f} ms)"
        )
        print(
            json.dumps(
                {
                    "metric": "trace_cycle_p99_ms",
                    "value": round(float(overall_p99), 3),
                    "unit": "ms",
                    "backend": backend,
                    "trace_seed": tcfg.seed,
                    "trace_digest": trace.digest(),
                    "trace_events": report.events_replayed,
                    "trace_parity_checks": report.parity_checks,
                    "trace_retraces": report.retraces,
                    "trace_band_p99_ms": {
                        b: (None if v is None else round(v, 3))
                        for b, v in band_p99.items()
                    },
                    "trace_rpc_p99_ms": {
                        r: (None if v is None else round(v, 3))
                        for r, v in rpc_p99.items()
                    },
                    "trace_slo": [v.to_doc() for v in verdicts],
                    "trace_slo_pass": slo_mod.slos_pass(verdicts),
                    "trace_nodes": tcfg.nodes,
                    "trace_pods": tcfg.pod_slots,
                    "trace_overhead_p99_pct": round(overhead_pct, 3),
                    "assembled_traces": assembled_traces,
                    "orphan_spans": orphan_spans,
                }
            ),
            flush=True,
        )
        return

    if config == "chaos-trace":
        # ISSUE 13: the chaos x trace gate (ROADMAP 5(c)) — a seeded
        # realistic trace replays through the full serving path while
        # the chaos harness injects a launch-failure burst (the
        # breaker must trip, brownout must serve bounded-staleness
        # degraded Scores, a half-open probe must recover) and an
        # in-process leader kill + journal warm-restart mid-replay
        # (recovery_ms measured client-side), followed by an overload
        # band storm (free sheds absorb, prod p99 holds).  Judged by
        # the obs/slo.py spec set INCLUDING a recovery-time SLO, with
        # post-convergence digest parity vs the unfaulted oracle and
        # zero warm-path retraces after recovery.
        import tempfile

        from koordinator_tpu.harness.chaos import (
            ChaosTraceReplay,
            chaos_trace_slo_specs,
            overload_band_storm,
        )
        from koordinator_tpu.harness.trace import (
            TraceConfig,
            generate_trace,
        )
        from koordinator_tpu.obs import slo as slo_mod
        from koordinator_tpu.obs.scorer_metrics import TRACE_CYCLE
        from koordinator_tpu.obs.slo import SloSpec

        def _env_int(name, default):
            # `or`: empty value means unset (the KOORD_* convention)
            return int(os.environ.get(name) or default)

        on_cpu = backend == "cpu"
        pod_slots = max(16, _env_int(
            "KOORD_BENCH_CHAOS_PODS", 96 if on_cpu else 512
        ))
        gang_min_member = 4
        gangs = max(1, min(6, pod_slots // (4 * gang_min_member)))
        tcfg = TraceConfig(
            seed=_env_int("KOORD_BENCH_CHAOS_SEED", 0),
            nodes=_env_int(
                "KOORD_BENCH_CHAOS_NODES", 32 if on_cpu else 128
            ),
            pod_slots=pod_slots,
            tenants=max(2, min(6, pod_slots // 24)),
            gangs=gangs,
            gang_min_member=gang_min_member,
            events=max(8, _env_int(
                "KOORD_BENCH_CHAOS_EVENTS", 24 if on_cpu else 48
            )),
        )
        trace = generate_trace(tcfg)
        events = len(trace.events)
        fail_at = max(1, events // 4)
        kill_at = max(fail_at + 4, (2 * events) // 3)
        phase(
            "chaos_trace_generated",
            events=events,
            digest=trace.digest()[:12],
            fail_at=fail_at,
            kill_at=kill_at,
        )
        from koordinator_tpu.obs import assemble as assemble_mod

        with tempfile.TemporaryDirectory(prefix="koord-bench-chaos-") as td:
            # tracing ON (ISSUE 14): the client, the leader AND its
            # warm-restarted successor all export spans to one
            # directory; the assembly below must reconstruct every
            # client-observed RPC across the kill
            trace_dir = os.path.join(td, "traces")
            report = ChaosTraceReplay(
                trace, td, fail_at=fail_at, fail_n=4, kill_at=kill_at,
                trace_export=trace_dir,
            ).run()
            assembly = assemble_mod.assemble([trace_dir])
            assembled_traces = len(assembly.traces)
            orphan_spans = len(assembly.orphan_spans)
            client_orphans = len(assembly.client_orphans)
        phase(
            "chaos_trace_replayed",
            rpc_errors=report.rpc_errors,
            degraded=report.degraded_replies,
            breaker_trips=report.breaker_trips,
            recovery_ms=(
                None if report.recovery_ms is None
                else round(report.recovery_ms, 1)
            ),
            retraces=report.retraces,
        )
        # the hard invariants fail the stage honestly — no artifact on
        # a broken contract (the parent's error artifact says why)
        assert report.parity_ok, (
            f"post-convergence parity vs the unfaulted oracle failed: "
            f"{report.parity_detail}"
        )
        assert report.retraces == 0, (
            f"{report.retraces} warm-path retrace(s) after recovery"
        )
        assert report.recovery_ms is not None, "leader kill never recovered"
        assert report.breaker_trips > 0, (
            "the injected launch-failure burst never tripped the breaker"
        )
        assert report.degraded_replies > 0, (
            "the brownout cache never served a degraded reply"
        )
        # the tracing gate (ISSUE 14): every client-observed RPC —
        # retried, shed, brownout-degraded, across the kill — must
        # assemble into a complete tree with zero orphan client spans
        assert assembled_traces > 0, "chaos replay exported no traces"
        assert client_orphans == 0, (
            f"{client_orphans} orphan client span(s) after assembling "
            "the chaos replay's exports"
        )
        verdicts = slo_mod.evaluate_slos(
            report.registry, chaos_trace_slo_specs(report.bands)
        )
        # overload band storm: free sheds absorb, prod p99 holds
        storm = overload_band_storm()
        phase(
            "band_storm",
            served=storm["served"],
            shed_by_band=storm["shed_by_band"],
        )
        assert storm["shed_by_band"].get("koord-free", 0) > 0, (
            "the overload storm shed nothing in the free band"
        )
        assert storm["shed_by_band"].get("koord-prod", 0) == 0, (
            "prod-band requests shed under a storm the free band "
            "should have absorbed"
        )
        # `or`: empty env value means unset (the KOORD_* convention)
        prod_p99_ms = float(
            os.environ.get("KOORD_CHAOS_PROD_P99_MS") or "2000"
        )
        verdicts.extend(slo_mod.evaluate_slos(storm["registry"], [
            SloSpec(
                name="prod-storm-score-p99",
                family=TRACE_CYCLE,
                quantile=0.99,
                threshold_ms=prod_p99_ms,
                labels={"band": "koord-prod", "rpc": "score"},
            ),
        ]))
        gate_pass = slo_mod.slos_pass(verdicts)
        # the per-band shed ladder outcome, merged: the replay's sheds
        # (usually none — it is serial) plus the storm's
        shed_by_band = dict(report.shed_by_band)
        for b, n in storm["shed_by_band"].items():
            shed_by_band[b] = shed_by_band.get(b, 0) + n
        print(
            json.dumps(
                {
                    "metric": "chaos_trace_recovery_ms",
                    "value": round(float(report.recovery_ms), 3),
                    "unit": "ms",
                    "backend": backend,
                    "chaos_trace_events": report.events_replayed,
                    "chaos_trace_seed": tcfg.seed,
                    "chaos_trace_digest": trace.digest(),
                    "chaos_trace_errors": report.rpc_errors,
                    "degraded_replies": report.degraded_replies,
                    "breaker_trips": report.breaker_trips,
                    "recovery_ms": round(float(report.recovery_ms), 3),
                    "chaos_trace_retraces": report.retraces,
                    "shed_by_band": shed_by_band,
                    "storm_band_p99_ms": {
                        b: (None if v is None else round(v, 3))
                        for b, v in storm["band_p99_ms"].items()
                    },
                    "chaos_trace_slo": [v.to_doc() for v in verdicts],
                    "chaos_trace_slo_pass": gate_pass,
                    "assembled_traces": assembled_traces,
                    "orphan_spans": orphan_spans,
                }
            ),
            flush=True,
        )
        return

    if config == "spark":
        # BASELINE config #1: exact NodeScoreList parity on the 3-node
        # spark-jobs example (reference examples/spark-jobs), scored by the
        # device kernel vs the sequential reference oracle
        from koordinator_tpu.harness.reference import ReferenceCycle
        from koordinator_tpu.solver import score_cycle

        nodes, pods, gangs, quotas = generators.spark_colocation()
        snap = encode_snapshot(nodes, pods, gangs, [])
        scores, feasible = score_cycle(snap)
        scores_np = np.asarray(scores)
        feasible_np = np.asarray(feasible)

        oracle = ReferenceCycle(
            [res.resource_vector(n["allocatable"]) for n in nodes],
            [[0] * res.NUM_RESOURCES for _ in nodes],
            [res.resource_vector(n.get("usage", {})) for n in nodes],
            [bool(n.get("metric_fresh", True)) for n in nodes],
        )
        P, N = len(pods), len(nodes)
        parity = True
        for p in range(P):
            req = res.resource_vector(pods[p]["requests"])
            est = _est(pods[p])
            for n in range(N):
                want = oracle.combined_score(n, req, est)
                want_ok = oracle.fit_ok(n, req) and oracle.loadaware_filter_ok(n)
                if int(scores_np[p, n]) != want or bool(
                    feasible_np[p, n]
                ) != bool(want_ok):
                    parity = False
                    phase(
                        "parity_mismatch",
                        pod=p,
                        node=n,
                        got=int(scores_np[p, n]),
                        want=want,
                    )
        assert parity, "spark NodeScoreList parity failed"
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            s, f = score_cycle(snap)
            np.asarray(s)
            times.append(_ms(t0))
        print(
            json.dumps(
                {
                    "metric": "spark_3node_score_ms",
                    "value": round(min(times), 3),
                    "unit": "ms",
                    "parity": "exact",
                    "backend": backend,
                }
            ),
            flush=True,
        )
        return

    if config == "gang":
        # BASELINE config #3: Coscheduling gang masks at 5k pods x 500
        # nodes (minMember=8), full cycle on the device
        from koordinator_tpu.solver import run_cycle

        nodes, pods, gangs, quotas = generators.gang_batch(
            pods=5000, nodes=500, min_member=8
        )
        snap = encode_snapshot(
            nodes, pods, gangs, [], node_bucket=500, pod_bucket=5000
        )
        from koordinator_tpu.solver import pallas_inputs_fit_i32

        i32_ok = bool(pallas_inputs_fit_i32(snap))
        t0 = time.perf_counter()
        result = run_cycle(snap, i32_ok=i32_ok)
        np.asarray(result.assignment)
        compile_ms = _ms(t0)
        phase("compile", ms=compile_ms, path=result.path)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            result = run_cycle(snap, i32_ok=i32_ok)
            np.asarray(result.assignment)
            times.append(_ms(t0))
        assignment = np.asarray(result.assignment)[: len(pods)]
        status = np.asarray(result.status)[: len(pods)]
        # gang all-or-nothing invariant: members of a gang below minMember
        # are WAIT_GANG, satisfied gangs' assigned members are ASSIGNED
        gang_ids = np.asarray(
            [
                int(p["gang"].split("-")[1]) if "gang" in p else -1
                for p in pods
            ]
        )
        violations = 0
        for g in range(len(gangs)):
            members = gang_ids == g
            placed = members & (assignment >= 0)
            if placed.sum() >= gangs[g]["min_member"]:
                violations += int((status[placed] != 0).sum())
            else:
                violations += int((status[placed] != 2).sum())
        assert violations == 0, f"{violations} gang-status violations"
        print(
            json.dumps(
                {
                    "metric": "gang_5kpod_500node_ms",
                    "value": round(min(times), 2),
                    "unit": "ms",
                    "backend": backend,
                    "path": result.path,
                    "assigned": int((assignment >= 0).sum()),
                    "gangs_ok": True,
                }
            ),
            flush=True,
        )
        return

    if config == "loadaware":
        # BASELINE config #2: LoadAware + Fit joint cycle, 1k pods x 200
        # nodes, with the measured native sequential baseline for speedup
        from koordinator_tpu.solver import run_cycle

        nodes, pods, gangs, quotas = generators.loadaware_joint(
            pods=1000, nodes=200
        )
        snap = encode_snapshot(
            nodes, pods, gangs, [], node_bucket=200, pod_bucket=1000
        )
        from koordinator_tpu.solver import pallas_inputs_fit_i32

        i32_ok = bool(pallas_inputs_fit_i32(snap))
        t0 = time.perf_counter()
        result = run_cycle(snap, i32_ok=i32_ok)
        np.asarray(result.assignment)
        phase("compile", ms=_ms(t0), path=result.path)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            result = run_cycle(snap, i32_ok=i32_ok)
            np.asarray(result.assignment)
            times.append(_ms(t0))
        cpu_ms = None
        native_assign = None
        try:
            cpu_ms, native_assign, _ = _native_baseline(
                nodes, pods, gangs, quotas
            )
        except Exception as exc:  # noqa: BLE001
            phase("cpu_native_baseline_failed", error=str(exc)[:200])
        if native_assign is not None:
            # placement parity native vs device — OUTSIDE the best-effort
            # try: a real divergence must fail the bench, not be logged as
            # a baseline hiccup while still publishing the speedup
            got = np.asarray(result.assignment)[: len(pods)].tolist()
            assert native_assign == got, "native/device placement divergence"
        ms = min(times)
        print(
            json.dumps(
                {
                    "metric": "loadaware_1kpod_200node_ms",
                    "value": round(ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "path": result.path,
                    "assigned": int(
                        (np.asarray(result.assignment)[: len(pods)] >= 0).sum()
                    ),
                    "cpu_native_baseline_ms": cpu_ms,
                    "vs_cpu_native": round(cpu_ms / ms, 3) if cpu_ms else None,
                }
            ),
            flush=True,
        )
        return

    if config == "extras":
        # the composed extended-plugin cycle: REAL NUMA/reservation/
        # deviceshare tensors (round-4 review #6 replaced the random
        # stand-ins) riding the kernel at benchmark scale, with the C++
        # baseline independently re-deriving the same mask/scores from
        # the raw subsystem tables and agreeing pod-for-pod
        from koordinator_tpu.constraints import build_quota_table_inputs
        from koordinator_tpu.harness.extras_scenario import (
            extras_scenario,
            plugin_extra_tensors,
            write_extras_file,
        )
        from koordinator_tpu.solver import greedy_assign
        from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

        from koordinator_tpu.solver import pallas_inputs_fit_i32

        del build_quota_table_inputs, encode_snapshot  # via the ONE recipe

        # the scenario mutates nodes/pods (device resources on both) so
        # every plugin leg is load-bearing; the lists are encoded ONCE,
        # through the same recipe the headline snapshot uses
        nodes, pods, gangs, quotas = generators.quota_colocation(
            pods=PODS, nodes=NODES
        )
        t0 = time.perf_counter()
        zones, policy, devices, rsv, nodes, pods = extras_scenario(
            nodes, pods, seed=0, node_bucket=NODES, pod_bucket=PODS,
        )
        snap, qdicts = generators.encode_quota_lists(
            nodes, pods, gangs, quotas, node_bucket=NODES, pod_bucket=PODS
        )
        phase("extras_encode", ms=_ms(t0))
        if backend != "cpu":
            assert pallas_inputs_fit_i32(snap), "snapshot out of i32 range"
        t0 = time.perf_counter()
        xmask, xscore = plugin_extra_tensors(snap, zones, policy, devices, rsv)
        phase("extras_tensors", ms=_ms(t0))
        run = (
            greedy_assign_dense if backend != "cpu" else greedy_assign
        )
        t0 = time.perf_counter()
        result = run(snap, extra_mask=xmask, extra_scores=xscore)
        np.asarray(result.assignment)
        phase("compile", ms=_ms(t0), path=result.path)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            result = run(snap, extra_mask=xmask, extra_scores=xscore)
            np.asarray(result.assignment)
            times.append(_ms(t0))
        assignment = np.asarray(result.assignment)[: len(pods)]
        assert int((assignment >= 0).sum()) > 0, "extras cycle assigned nothing"
        assert result.path == ("pallas" if backend != "cpu" else "scan")

        # independent-implementation parity (best-effort metric, HARD
        # parity): the C++ binary recomputes the extras from raw tables
        native_ms = None
        native_parity = None
        try:
            import tempfile

            with tempfile.TemporaryDirectory() as tmp:
                binary, golden = _native_prepare(nodes, pods, gangs, quotas, tmp)
                extras_path = os.path.join(tmp, "extras.bin")
                from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG

                write_extras_file(
                    extras_path, zones, policy, devices, rsv,
                    np.asarray(DEFAULT_CYCLE_CONFIG.fit_weights_arr()),
                )
                out = subprocess.run(
                    [binary, golden, "1", "1", extras_path],
                    capture_output=True,
                    text=True,
                    timeout=600,
                    check=True,
                )
                lines = out.stdout.splitlines()
                native_ms = json.loads(lines[0])["value"]
                native_assign = [int(v) for v in lines[1].split()[1:]]
                native_parity = native_assign[: len(pods)] == assignment.tolist()
        except Exception as exc:  # noqa: BLE001
            phase("extras_native_failed", error=str(exc)[:200])
        if native_parity is not None:
            assert native_parity, "extras native/device placement divergence"
        print(
            json.dumps(
                {
                    "metric": "extras_10kpod_2knode_ms",
                    "value": round(min(times), 2),
                    "unit": "ms",
                    "backend": backend,
                    "path": result.path,
                    "assigned": int((assignment >= 0).sum()),
                    "cpu_native_extras_ms": native_ms,
                    "native_parity": native_parity,
                }
            ),
            flush=True,
        )
        return

    if config == "plugins":
        # ISSUE 15: the fused scoring-term registry vs the way the Go
        # reference would run it — one dense launch carrying
        # heterogeneity + sensitivity + packing vs a naive per-term-
        # SEQUENTIAL-launch oracle (base Filter+Score pass, then one
        # launch per term, then host-side combination), digest-
        # identical, plus the warm delta/Score stream with every term
        # enabled (zero jit cache misses, O(dirty) rescoring).
        import dataclasses as _dc

        import jax.numpy as jnp

        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.config import CycleConfig
        from koordinator_tpu.solver import (
            masked_top_k,
            score_cycle,
            score_upper_bound,
        )
        from koordinator_tpu.solver.terms import (
            default_term_config,
            term_extras,
            term_names,
        )

        rng = np.random.default_rng(0)
        C_, A_ = 4, 3
        nodes, pods, gangs, quotas = generators.quota_colocation(
            pods=PODS, nodes=NODES
        )
        t0 = time.perf_counter()
        snap, _q = generators.encode_quota_lists(
            nodes, pods, gangs, quotas, node_bucket=NODES, pod_bucket=PODS
        )
        NB = snap.nodes.allocatable.shape[0]
        PB = snap.pods.capacity
        accel = jnp.asarray((np.arange(NB) % A_).astype(np.int32))
        wclass = jnp.asarray((np.arange(PB) % C_).astype(np.int32))
        sens_np = np.zeros((PB, res.NUM_RESOURCES), np.int64)
        sens_np[:, 0] = rng.integers(0, 101, PB)
        sens_np[:, 1] = rng.integers(0, 101, PB)
        tput_np = rng.integers(0, 101, (C_, A_)).astype(np.int64)
        snap = _dc.replace(
            snap,
            nodes=_dc.replace(snap.nodes, accel_type=accel),
            pods=_dc.replace(
                snap.pods,
                workload_class=wclass,
                sensitivity=jnp.asarray(sens_np),
            ),
            throughput=jnp.asarray(tput_np),
        )
        phase("plugins_encode", ms=_ms(t0), classes=C_, accels=A_)

        cfg_terms = default_term_config(
            packing_headroom={"cpu": 98, "memory": 98}
        )
        cfg_base = CycleConfig()
        # the sequential oracle runs the scorer the way the Go
        # reference runs its plugin chain: one pods x nodes pass PER
        # PLUGIN — NodeResourcesFit, LoadAware, then each registry term
        # — each materializing its own [P, N] tensor, combined
        # afterwards.  The fused engine folds all five into the ONE
        # score_cycle program.  score_all is additive in the plugin
        # weights and the masks AND, so the combination is
        # digest-identical by construction (asserted below).
        cfg_fit = _dc.replace(cfg_base, enable_loadaware=False)
        cfg_la = _dc.replace(cfg_base, enable_fit_score=False)
        seq_term_cfgs = [
            _dc.replace(cfg_base, heterogeneity=cfg_terms.heterogeneity),
            _dc.replace(cfg_base, sensitivity=cfg_terms.sensitivity),
            _dc.replace(cfg_base, packing=cfg_terms.packing),
        ]
        k = 32
        hi = score_upper_bound(cfg_terms)
        from koordinator_tpu.solver.topk import masked_top_k_host

        def fused():
            # the REAL serving shape (ISSUE 15): ONE launch carries
            # every plugin and term, the device top-k runs over the
            # fused total, and only the [P, k] prefix crosses back to
            # host — zero extra launches, zero extra readbacks
            s, f = score_cycle(snap, cfg_terms)
            ts, ti = masked_top_k(s, f, k=k, hi=hi)
            return jax.device_get((ts, ti))

        def oracle():
            # the per-plugin-sequential alternative (the way the Go
            # reference runs its plugin chain, lifted to tensors):
            # every plugin/term is its OWN launch materializing its
            # own [P, N] matrix, and — because no fused total exists
            # on device — each matrix pays the full device->host
            # readback, the combination runs host-side, and so must
            # the serving top-k (masked_top_k_host, the bit-exact
            # twin).  Digest-identical replies, several launches and
            # O(P x N) readbacks per term more expensive.
            s, f = jax.device_get(score_cycle(snap, cfg_fit))
            s_la, f_la = jax.device_get(score_cycle(snap, cfg_la))
            s = s + s_la
            f = f & f_la
            for tcfg in seq_term_cfgs:
                xs, xm = term_extras(snap, tcfg)
                if xs is not None:
                    s = s + jax.device_get(xs)
                if xm is not None:
                    f = f & jax.device_get(xm)
            return masked_top_k_host(s, f, k)

        def base():
            # the pre-ISSUE serving launch (no terms): fit+loadaware,
            # device top-k, k-prefix readback — the shared floor BOTH
            # engines pay identically (the CPU backend is compute-bound
            # on the base plugins' integer division, so it dominates
            # both end-to-end walls)
            s, f = score_cycle(snap, cfg_base)
            ts, ti = masked_top_k(
                s, f, k=k, hi=score_upper_bound(cfg_base)
            )
            return jax.device_get((ts, ti))

        def digest(tsti):
            ts, ti = tsti
            return (
                np.asarray(ts, np.int64).tobytes()
                + np.asarray(ti, np.int32).tobytes()
            )

        t0 = time.perf_counter()
        f_out = fused()
        phase("plugins_fused_compile", ms=_ms(t0))
        t0 = time.perf_counter()
        o_out = oracle()
        phase("plugins_oracle_compile", ms=_ms(t0))
        base()
        assert digest(f_out) == digest(o_out), (
            "fused engine reply diverged from the per-term-sequential "
            "oracle"
        )
        fused_times, oracle_times, base_times = [], [], []
        for _ in range(3):
            t0 = time.perf_counter()
            f_out = fused()
            fused_times.append(_ms(t0))
            t0 = time.perf_counter()
            o_out = oracle()
            oracle_times.append(_ms(t0))
            base_times.append(_timed(base))
            assert digest(f_out) == digest(o_out)
        fused_ms = min(fused_times)
        oracle_ms = min(oracle_times)
        base_ms = min(base_times)
        phase("plugins_walls", fused_ms=round(fused_ms, 2),
              oracle_ms=round(oracle_ms, 2), base_ms=round(base_ms, 2))
        # the headline ratio isolates what the registry CHANGED — the
        # cost of carrying the three policies:
        #   sequential: per-term launches + full [P, N] readbacks +
        #               host combine + host top-k  (oracle - base)
        #   fused:      the marginal cost inside the ONE launch
        #               (fused - base; het/sens fuse to ~free, packing
        #               adds its one division pass)
        # End-to-end walls are published unreduced alongside; on CPU
        # they sit ~1.4x apart because the base plugins' integer
        # division dominates both (the mesh_speedup precedent — a TPU
        # round sees the launch/readback economics end to end).
        # noise floor tied to the measured scale (2% of the base wall,
        # >= 1 ms): with min-of-3 jitter a near-free fused marginal
        # could land at or below zero, and dividing by a fixed tiny
        # floor would fabricate an arbitrarily large headline from
        # noise — below the floor the marginal reads "at or below
        # measurement noise" (phase-logged), bounding the published
        # ratio at oracle_marginal / floor
        noise_floor = max(0.02 * base_ms, 1.0)
        fused_marginal = fused_ms - base_ms
        if fused_marginal < noise_floor:
            phase("plugins_fused_marginal_below_noise",
                  fused_marginal_ms=round(fused_marginal, 2),
                  noise_floor_ms=round(noise_floor, 2))
            fused_marginal = noise_floor
        oracle_marginal = max(oracle_ms - base_ms, 0.0)
        speedup = oracle_marginal / fused_marginal
        phase("plugins_measured", fused_ms=round(fused_ms, 2),
              oracle_ms=round(oracle_ms, 2), speedup=round(speedup, 2))

        # warm incremental stream with ALL terms enabled: the same
        # probe the headline publishes, under retrace_guard(0) — the
        # term-enabled warm path must hold zero jit cache misses and
        # rescore only the dirty columns
        from koordinator_tpu.harness.golden import build_sync_request

        sync_req, _qids = build_sync_request(
            nodes, pods, gangs, quotas,
            node_bucket=NODES, pod_bucket=PODS,
        )
        sync_req.nodes.accel_type.extend(
            int(v) for v in np.asarray(accel)[: len(nodes)]
        )
        sync_req.pods.workload_class.extend(
            int(v) for v in np.asarray(wclass)[: len(pods)]
        )
        sync_req.pods.sensitivity.CopyFrom(
            numpy_to_tensor(sens_np[: len(pods)])
        )
        sync_req.terms.throughput.CopyFrom(numpy_to_tensor(tput_np))
        warm_ms, full_warm_ms, warm_speedup, cols_mean = _incr_score_probe(
            sync_req.SerializeToString(), cfg=cfg_terms, guard=True,
        )
        phase("plugins_warm", warm_score_ms=round(warm_ms, 2),
              cols=cols_mean)
        print(
            json.dumps(
                {
                    "metric": "plugin_fused_speedup",
                    "value": round(speedup, 3),
                    "unit": "x",
                    "backend": backend,
                    "nodes": NODES,
                    "pods": PODS,
                    "plugin_terms": len(term_names(cfg_terms)),
                    "plugin_fused_speedup": round(speedup, 3),
                    "plugin_fused_ms": round(fused_ms, 2),
                    "plugin_oracle_ms": round(oracle_ms, 2),
                    "plugin_base_ms": round(base_ms, 2),
                    "plugin_warm_score_ms": round(warm_ms, 2),
                    "warm_score_ms": round(warm_ms, 2),
                    "full_warm_score_ms": round(full_warm_ms, 2),
                    "incr_score_speedup": round(warm_speedup, 2),
                    "incr_cols_rescored": round(cols_mean, 2),
                }
            ),
            flush=True,
        )
        return

    if config == "sparse":
        # ISSUE 16: sparse candidate-set scoring — break the dense
        # [P, N] wall.  Three stages: (1) the headline scale point,
        # pods x nodes big enough that the dense pass cannot even
        # allocate its [P, N, R] broadcast temporaries (dense_score_ms
        # publishes the literal "OOM" — the RAM gate refuses to hand
        # the OS an allocation it would kill the process over), while
        # the sparse engine builds candidates in O(P x B) memory and
        # serves [P, C]; (2) a medium scale where BOTH engines fit, so
        # sparse_speedup is a measured ratio over identical replies;
        # (3) a servicer-level warm delta/Score stream with the sparse
        # engine on, digest-compared to the dense servicer per rep
        # under retrace_guard(0), publishing candidate_refresh_total.
        import jax.numpy as jnp

        from koordinator_tpu.config import CycleConfig
        from koordinator_tpu.model.snapshot import (
            ClusterSnapshot,
            GangTable,
            NodeBatch,
            PodBatch,
            QuotaTable,
        )
        from koordinator_tpu.solver import (
            build_candidates,
            masked_top_k,
            score_candidates,
            score_cycle,
            score_upper_bound,
            sparse_top_k,
        )

        R = res.NUM_RESOURCES
        _CPU_I = res.RESOURCE_INDEX[res.CPU]
        _MEM_I = res.RESOURCE_INDEX[res.MEMORY]
        _PODS_I = res.RESOURCE_INDEX[res.PODS]
        WIDTH = int(os.environ.get("KOORD_BENCH_SPARSE_WIDTH") or 256)
        S_NODES = int(
            os.environ.get("KOORD_BENCH_SPARSE_NODES") or (1 << 21)
        )
        S_PODS = int(os.environ.get("KOORD_BENCH_SPARSE_PODS") or 512)
        k = 32
        cfg_sparse = CycleConfig(candidate_width=WIDTH)
        cfg_dense = CycleConfig()
        hi = score_upper_bound(cfg_dense)

        def sparse_snapshot(n, p, n_open, seed):
            """Narrow-feasibility cluster straight from numpy arrays
            (no per-node python dicts — the whole point is a node count
            the dict-based generators would crawl over): exactly
            ``n_open`` nodes have headroom for the uniform pods, the
            rest sit requested-to-the-brim, so every pod's exact
            feasible count is ``n_open`` — the regime the sparse
            engine exists for."""
            rng = np.random.default_rng(seed)
            nalloc = np.zeros((n, R), np.int64)
            nalloc[:, _CPU_I] = 32_000
            nalloc[:, _MEM_I] = 128 * 1024
            nalloc[:, _PODS_I] = 256
            nreq = np.zeros((n, R), np.int64)
            nreq[:, _CPU_I] = 31_800  # 200m free < the 500m ask
            open_rows = rng.choice(n, size=n_open, replace=False)
            nreq[open_rows, _CPU_I] = 0
            nuse = (nalloc * 0.3).astype(np.int64)
            preq = np.zeros((p, R), np.int64)
            preq[:, _CPU_I], preq[:, _MEM_I] = 500, 512
            preq[:, _PODS_I] = 1
            return ClusterSnapshot(
                nodes=NodeBatch(
                    allocatable=jnp.asarray(nalloc),
                    requested=jnp.asarray(nreq),
                    usage=jnp.asarray(nuse),
                    metric_fresh=jnp.ones(n, bool),
                    valid=jnp.ones(n, bool),
                ),
                pods=PodBatch(
                    requests=jnp.asarray(preq),
                    estimated=jnp.asarray(preq),
                    priority_class=jnp.zeros(p, np.int32),
                    qos=jnp.zeros(p, np.int32),
                    priority=jnp.full(p, 5000, np.int32),
                    gang_id=jnp.full(p, -1, np.int32),
                    quota_id=jnp.full(p, -1, np.int32),
                    valid=jnp.ones(p, bool),
                ),
                gangs=GangTable(
                    min_member=jnp.zeros(1, np.int32),
                    valid=jnp.zeros(1, bool),
                ),
                quotas=QuotaTable(
                    runtime=jnp.zeros((1, R), np.int64),
                    used=jnp.zeros((1, R), np.int64),
                    limited=jnp.zeros((1, R), bool),
                    valid=jnp.zeros(1, bool),
                ),
            )

        def dense_once(snap):
            s, f = score_cycle(snap, cfg_dense)
            ts, ti = masked_top_k(s, f, k=k, hi=hi)
            return jax.device_get((ts, ti))

        def sparse_once(snap, cand):
            s, f = score_candidates(snap, cand, cfg_sparse)
            ts, ti, _ok = sparse_top_k(s, f, cand, k=k, hi=hi)
            return jax.device_get((ts, ti))

        # -- stage 1: the scale point the dense path cannot allocate --
        snap_big = sparse_snapshot(
            S_NODES, S_PODS, n_open=max(1, WIDTH // 2), seed=16
        )
        phase("sparse_encode", nodes=S_NODES, pods=S_PODS, width=WIDTH)
        # the dense pass materializes [P, N, R] i64 broadcast
        # temporaries (LoadAware's usage selection) on top of a
        # handful of [P, N] i64 tensors; refusing past 75% of free
        # RAM records "OOM" WITHOUT attempting — handing the OS that
        # allocation gets the bench OOM-killed, not a measurement
        dense_peak = S_PODS * S_NODES * 8 * (R + 4)
        avail = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_AVPHYS_PAGES")
        if dense_peak > 0.75 * avail:
            dense_ms = "OOM"
            phase("sparse_dense_oom", dense_peak_gib=round(dense_peak / 2**30, 1),
                  avail_gib=round(avail / 2**30, 1))
        else:
            dense_once(snap_big)
            dense_ms = round(min(_timed(lambda: dense_once(snap_big))
                                 for _ in range(3)), 3)
            phase("sparse_dense_fits", dense_score_ms=dense_ms)
        t0 = time.perf_counter()
        cand_b, count_b = build_candidates(snap_big, cfg_sparse)
        jax.block_until_ready(cand_b)
        build_ms = _ms(t0)  # cold: includes the blocked sweep's compile
        assert int(jax.device_get(count_b).max()) <= WIDTH, (
            "bench cluster overflowed its own candidate width"
        )
        sparse_once(snap_big, cand_b)  # compile the serving pair
        sparse_ms = min(
            _timed(lambda: sparse_once(snap_big, cand_b)) for _ in range(3)
        )
        phase("sparse_walls", sparse_score_ms=round(sparse_ms, 3),
              sparse_build_ms=round(build_ms, 1))

        # -- stage 2: sparse vs dense where both fit, identical replies --
        snap_mid = sparse_snapshot(4096, 512, n_open=WIDTH // 2, seed=17)
        cand_m, _count_m = build_candidates(snap_mid, cfg_sparse)
        d_out = dense_once(snap_mid)
        s_out = sparse_once(snap_mid, cand_m)
        assert np.array_equal(d_out[0], s_out[0]) and np.array_equal(
            np.asarray(d_out[1], np.int64), np.asarray(s_out[1], np.int64)
        ), "sparse top-k diverged from the dense oracle at C >= feasible"
        dense_mid = min(_timed(lambda: dense_once(snap_mid))
                        for _ in range(3))
        sparse_mid = min(_timed(lambda: sparse_once(snap_mid, cand_m))
                         for _ in range(3))
        speedup = dense_mid / max(sparse_mid, 1e-6)
        phase("sparse_speedup", dense_mid_ms=round(dense_mid, 3),
              sparse_mid_ms=round(sparse_mid, 3),
              speedup=round(speedup, 2))

        # -- stage 3: servicer warm stream, sparse vs dense reply bytes --
        from koordinator_tpu.analysis import retrace_guard
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.obs.scorer_metrics import CANDIDATE_REFRESH

        nl, pl, gl, ql = generators.quota_colocation(pods=128, nodes=64)
        sync_req, _qids = build_sync_request(
            nl, pl, gl, ql, node_bucket=64, pod_bucket=128
        )
        payload = sync_req.SerializeToString()
        sp_sv = ScorerServicer(
            cfg=CycleConfig(candidate_width=64), score_memo=False
        )
        dn_sv = ScorerServicer(score_memo=False, score_incr=False)
        for sv in (sp_sv, dn_sv):
            sv.sync(pb2.SyncRequest.FromString(payload))

        def score_sv(sv):
            reply = sv.score(pb2.ScoreRequest(
                snapshot_id=sv.snapshot_id(), top_k=8, flat=True
            ))
            return reply.flat.SerializeToString()

        base_req = np.asarray(sp_sv.state.node_requested, np.int64).copy()
        rows = np.arange(0, base_req.shape[0], 9)

        def delta_sv(rep):
            prev = base_req.copy()
            base_req[rows, 0] += 1 + rep
            warm = pb2.SyncRequest()
            warm.nodes.requested.CopyFrom(numpy_to_tensor(base_req, prev))
            raw = warm.SerializeToString()
            for sv in (sp_sv, dn_sv):
                sv.sync(pb2.SyncRequest.FromString(raw))
                assert sv.state.last_sync_path == "warm"

        # warm-up compiles cold + dirty-bucket shapes off the guard
        assert score_sv(sp_sv) == score_sv(dn_sv)
        delta_sv(0)
        assert score_sv(sp_sv) == score_sv(dn_sv)
        with retrace_guard(budget=0):
            for rep in range(1, 9):
                delta_sv(rep)
                assert score_sv(sp_sv) == score_sv(dn_sv), (
                    "sparse servicer reply diverged from the dense "
                    "servicer on the warm stream"
                )
        reg = sp_sv.telemetry.registry
        refresh_total = sum(
            int(reg.get(CANDIDATE_REFRESH, {"reason": r}) or 0)
            for r in ("cold", "dirty", "stale")
        )
        assert refresh_total >= 9, (
            f"warm stream refreshed candidates only {refresh_total} "
            "times — the dirty attribution is not reaching the lists"
        )
        phase("sparse_warm_stream", refresh_total=refresh_total)

        print(
            json.dumps(
                {
                    "metric": "sparse_score_ms",
                    "value": round(sparse_ms, 3),
                    "unit": "ms",
                    "backend": backend,
                    "nodes": S_NODES,
                    "pods": S_PODS,
                    "sparse_score_ms": round(sparse_ms, 3),
                    "sparse_build_ms": round(build_ms, 1),
                    "dense_score_ms": dense_ms,
                    "sparse_speedup": round(speedup, 3),
                    "candidate_width": WIDTH,
                    "candidate_refresh_total": int(refresh_total),
                }
            ),
            flush=True,
        )
        return

    if config == "smoke":
        # hardware smoke (round-3 review #9): a small-shape run through the
        # REAL Mosaic lowering (not interpret mode) asserting the pallas
        # path executed and its placements match the scan path bit-for-bit;
        # < 30 s wall, so every round has cheap proof the kernel still
        # lowers on hardware without paying the full bench
        from koordinator_tpu.solver import greedy_assign
        from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

        nodes, pods, gangs, quotas = generators.loadaware_joint(
            seed=5, pods=512, nodes=128
        )
        snap = encode_snapshot(
            nodes, pods, gangs, [], node_bucket=128, pod_bucket=512
        )
        interp = backend == "cpu"  # CPU fallback: interpret-mode parity only
        t0 = time.perf_counter()
        # the real hardware signal is that the compiled (non-interpret)
        # kernel executed without raising — greedy_assign_dense hardcodes
        # path="pallas", so asserting on it would be vacuous; "mode" in the
        # artifact records compiled vs interpret truthfully
        result = greedy_assign_dense(snap, interpret=interp)
        got = np.asarray(result.assignment)
        compile_ms = _ms(t0)
        want = np.asarray(greedy_assign(snap).assignment)
        parity = bool((got == want).all())
        assert parity, "smoke: pallas placements diverged from scan"
        t0 = time.perf_counter()
        r2 = greedy_assign_dense(snap, interpret=interp)
        np.asarray(r2.assignment)
        steady_ms = _ms(t0)

        print(
            json.dumps(
                {
                    "metric": "smoke_512pod_128node_ms",
                    "value": round(steady_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "path": result.path,
                    "mode": "interpret" if interp else "compiled",
                    "compile_ms": round(compile_ms, 1),
                    "parity": "exact",
                    "assigned": int((got[: len(pods)] >= 0).sum()),
                    "spans": {
                        "compile": round(compile_ms, 2),
                        "steady": round(steady_ms, 2),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "bridge":
        # the production seam end to end: a host scheduler's view — full
        # Sync then Assign through the REAL raw-UDS framing (the framing
        # the Go/C++ shims speak) at headline scale, so the number
        # includes serialization, the socket round trip, the device
        # cycle, and reply assembly
        import socket
        import struct
        import tempfile

        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.udsserver import (
            METHOD_ASSIGN,
            METHOD_SCORE,
            METHOD_SYNC,
            RawUdsServer,
        )
        from koordinator_tpu.constraints import build_quota_table_inputs
        from koordinator_tpu.harness.golden import build_sync_request

        _, nodes, pods, gangs, quotas, _ = _quota_snapshot(
            encode_snapshot, generators, res, build_quota_table_inputs
        )
        req, _ = build_sync_request(
            nodes, pods, gangs, quotas, node_bucket=NODES, pod_bucket=PODS
        )
        payload = req.SerializeToString()
        with tempfile.TemporaryDirectory() as tmp:
            sock_path = os.path.join(tmp, "scorer.sock")
            # Score memo AND incremental engine OFF for every storm
            # engine below: a storm against an unchanged snapshot would
            # otherwise serve from the (snapshot, config, k-bucket)
            # prefix memo after its first batch — and with the
            # incremental engine on, every post-first launch would
            # reuse the resident score tensors with an empty dirty set
            # (no scoring math at all).  The probe is here to measure
            # the DISPATCH engines, not the short-circuits (the memo
            # and the incremental engine have their own counters,
            # tests, and the incr_score probe above).
            from koordinator_tpu.bridge.server import ScorerServicer

            server = RawUdsServer(
                sock_path,
                servicer=ScorerServicer(score_memo=False, score_incr=False),
            )
            server.start()
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                conn.connect(sock_path)

                def call(method, body):
                    conn.sendall(
                        struct.pack(">BI", method, len(body)) + body
                    )
                    status, ln = struct.unpack(
                        ">BI", _recv_exact(conn, 5)
                    )
                    out = _recv_exact(conn, ln)
                    assert status == 0, out
                    return out

                t0 = time.perf_counter()
                sync = pb2.SyncReply.FromString(call(METHOD_SYNC, payload))
                sync_ms = _ms(t0)
                phase("sync", ms=round(sync_ms, 1), bytes=len(payload))

                def assign(snapshot_id):
                    areq = pb2.AssignRequest(
                        snapshot_id=snapshot_id
                    ).SerializeToString()
                    t0 = time.perf_counter()
                    reply = pb2.AssignReply.FromString(
                        call(METHOD_ASSIGN, areq)
                    )
                    return reply, _ms(t0)

                # first assign pays the compile (and the cold snapshot
                # build); everything after reuses the jit cache
                reply, _first_ms = assign(sync.snapshot_id)
                phase("first_assign", path=reply.path)

                # WARM cycles (the tentpole path): each rep ships a
                # sparse delta (a few node rows move, round-4 review #2)
                # that lands as an on-device scatter into the resident
                # tensors, then Assign runs straight off them — no host
                # re-encode, no full re-upload
                from koordinator_tpu.bridge.state import numpy_to_tensor

                prev_req = np.frombuffer(
                    req.nodes.requested.data, "<i8"
                ).reshape(tuple(req.nodes.requested.shape)).copy()
                delta_sync_ms = None
                warm_payload = b""
                warm_times = []
                for rep in range(3):
                    warm_req_arr = prev_req.copy()
                    warm_req_arr[:3, 0] += 500 + rep  # three nodes' cpu move
                    warm = pb2.SyncRequest()
                    warm.nodes.requested.CopyFrom(
                        numpy_to_tensor(warm_req_arr, prev_req)
                    )
                    warm_payload = warm.SerializeToString()
                    t0 = time.perf_counter()
                    sync = pb2.SyncReply.FromString(
                        call(METHOD_SYNC, warm_payload)
                    )
                    delta_ms = _ms(t0)
                    delta_sync_ms = (
                        delta_ms if delta_sync_ms is None
                        else min(delta_sync_ms, delta_ms)
                    )
                    prev_req = warm_req_arr
                    assert server.servicer.state.last_sync_path == "warm", (
                        "delta sync must land on the resident device tensors"
                    )
                    reply, ms = assign(sync.snapshot_id)
                    warm_times.append(ms)
                phase(
                    "warm_assign",
                    ms=round(min(warm_times), 2),
                    delta_sync_ms=round(delta_sync_ms, 2),
                    bytes=len(warm_payload),
                )
                assert len(warm_payload) < len(payload) // 100, (
                    "delta frame should be ~100x below the full sync"
                )

                # incremental score engine probe (ISSUE 9): the WARM
                # Score cost — until now only the Assign side had a
                # warm-path timing (warm_assign_ms), while the O(P x N)
                # rescore a warm Score pays went unmeasured.  Two
                # in-process servicers (engine on vs score_incr=False
                # oracle) replay the same <=64-dirty-node delta/Score
                # stream, digest-identity asserted per rep.  Best
                # effort: a probe failure publishes nulls, never kills
                # the bridge artifact.
                warm_score_ms = full_warm_score_ms = None
                incr_score_speedup = incr_cols_rescored = None
                try:
                    (warm_score_ms, full_warm_score_ms,
                     incr_score_speedup, incr_cols_rescored) = (
                        _incr_score_probe(payload)
                    )
                    phase(
                        "incr_score",
                        warm_score_ms=round(warm_score_ms, 2),
                        full_warm_score_ms=round(full_warm_score_ms, 2),
                        speedup=round(incr_score_speedup, 3),
                        cols=round(incr_cols_rescored, 1),
                    )
                except Exception as exc:  # noqa: BLE001
                    phase("incr_score_failed", error=str(exc)[:200])

                # COLD cycles (the pre-PR price of EVERY Assign): drop
                # the resident state so the next full Sync re-decodes
                # everything and Assign pays the host re-encode + full
                # upload before the device cycle
                from koordinator_tpu.bridge.state import ResidentState

                cold_times = []
                for _ in range(3):
                    server.servicer.state = ResidentState()
                    sync = pb2.SyncReply.FromString(call(METHOD_SYNC, payload))
                    assert server.servicer.state.last_sync_path == "cold"
                    reply, ms = assign(sync.snapshot_id)
                    cold_times.append(ms)
                phase("cold_assign", ms=round(min(cold_times), 2))

                assigned = sum(1 for a in reply.assignment if a >= 0)
                sreq = pb2.ScoreRequest(
                    snapshot_id=sync.snapshot_id, top_k=32, flat=True
                ).SerializeToString()
                t0 = time.perf_counter()
                score = pb2.ScoreReply.FromString(call(METHOD_SCORE, sreq))
                score_ms = _ms(t0)

                # concurrent-clients probe (ISSUE 5/6): a worker storm
                # firing flat top-32 Scores at once against THREE
                # engines on the same snapshot — the serialized
                # baseline (max_batch=1, depth=1: every request pays
                # its own launch AND its own blocking readback, the
                # pre-coalescing lock behavior), the ISSUE-5 coalescer
                # (shared launches, depth=1: the leader still blocks
                # the device section across its stacked readback), and
                # the main server's pipelined engine (depth-2 double
                # buffering + adaptive gather window).  Digest-identical
                # replies across all three.
                conc = int(os.environ.get("KOORD_BENCH_SCORE_CLIENTS", "64"))
                per_client = int(
                    os.environ.get("KOORD_BENCH_SCORE_REPS", "3")
                )

                def storm_server(name, **kwargs):
                    """Start a baseline server, sync the same snapshot,
                    return (server, snapshot_id)."""
                    path_ = os.path.join(tmp, f"{name}.sock")
                    srv = RawUdsServer(
                        path_, servicer=ScorerServicer(**kwargs)
                    ).start()
                    bconn = socket.socket(
                        socket.AF_UNIX, socket.SOCK_STREAM
                    )
                    bconn.connect(path_)
                    try:
                        bconn.sendall(
                            struct.pack(">BI", METHOD_SYNC, len(payload))
                            + payload
                        )
                        st, ln = struct.unpack(
                            ">BI", _recv_exact(bconn, 5)
                        )
                        sbody = _recv_exact(bconn, ln)
                        assert st == 0, sbody
                        sid_ = pb2.SyncReply.FromString(sbody).snapshot_id
                    finally:
                        bconn.close()
                    return srv, path_, sid_

                serial_server = coal_server = None
                try:
                    serial_server, serial_sock, serial_sid = storm_server(
                        "serial",
                        coalesce_max_batch=1,
                        coalesce_window_ms=0.0,
                        pipeline_depth=1,
                        score_memo=False,
                        score_incr=False,
                    )
                    coal_server, coal_sock, coal_sid = storm_server(
                        "coalesce_d1",
                        coalesce_max_batch=16,
                        coalesce_window_ms=0.0,
                        pipeline_depth=1,
                        score_memo=False,
                        score_incr=False,
                    )
                    # The serialized baseline processes strictly one
                    # request at a time (max_batch=1, depth=1), so its
                    # storm wall is just n_requests x the mean service
                    # time regardless of client fan-in.  On the CPU
                    # scan fallback a single 10k x 2k Score costs
                    # seconds, and the full 64 x reps baseline alone
                    # would blow the parent's child window (the
                    # rc=124-no-artifact class) — so on cpu we measure a
                    # small sample and extrapolate linearly, publishing
                    # the sample size in the artifact.  TPU rounds
                    # measure the full storm.
                    serial_clients, serial_reps = conc, per_client
                    if backend == "cpu":
                        serial_clients = min(conc, int(
                            os.environ.get("KOORD_BENCH_SERIAL_SAMPLE", "4")
                        ))
                        serial_reps = min(per_client, 2)
                    serial_n = serial_clients * serial_reps
                    wall_serial, lat_serial, dig_serial, errs = _score_storm(
                        serial_sock, serial_sid, serial_clients, serial_reps
                    )
                    assert not errs, f"serial storm errors: {errs}"
                    wall_serial = _extrapolate_serial(
                        wall_serial, serial_n, conc * per_client
                    )
                    wall_d1, _lat_d1, dig_d1, errs = _score_storm(
                        coal_sock, coal_sid, conc, per_client
                    )
                    assert not errs, f"depth-1 storm errors: {errs}"
                    stats_at_start = {}
                    wall_coal, lat_coal, dig_coal, errs = _score_storm(
                        sock_path, sync.snapshot_id, conc, per_client,
                        on_start=lambda: stats_at_start.update(
                            server.servicer.dispatch.stats()
                        ),
                    )
                    assert not errs, f"pipelined storm errors: {errs}"
                    before = stats_at_start
                    # every reply across all three servers decodes the
                    # same snapshot: the pipelined demux must be
                    # byte-identical with the serialized execution
                    assert (
                        len(dig_serial) == 1
                        and dig_serial == dig_coal == dig_d1
                    ), "storm replies diverged from serial execution"
                    after = server.servicer.dispatch.stats()
                    batches = after["batches"] - before["batches"]
                    coalesce_batch_mean = (
                        (after["requests"] - before["requests"]) / batches
                        if batches else 1.0
                    )
                    score_speedup = (
                        wall_serial / wall_coal if wall_coal > 0 else None
                    )
                    # the ISSUE-6 headline: pipelined vs the ISSUE-5
                    # coalescer (shared launches, serial readbacks)
                    pipeline_speedup = (
                        wall_d1 / wall_coal if wall_coal > 0 else None
                    )
                    # device idle while work was queued, across the
                    # pipelined storm only (stats diffed around it)
                    device_idle_ms = max(
                        0.0,
                        after["device_idle_ms"] - before["device_idle_ms"],
                    )
                    window_ms = after["window_ms"]
                    overlaps = (
                        after["launch_overlaps"] - before["launch_overlaps"]
                    )
                    p50 = lat_coal[len(lat_coal) // 2]
                    p99 = lat_coal[
                        min(len(lat_coal) - 1,
                            int(round(0.99 * (len(lat_coal) - 1))))
                    ]
                    phase(
                        "score_storm",
                        concurrency=conc,
                        serial_sample=serial_n,
                        serial_wall_ms=round(wall_serial * 1000.0, 1),
                        depth1_wall_ms=round(wall_d1 * 1000.0, 1),
                        pipelined_wall_ms=round(wall_coal * 1000.0, 1),
                        speedup=(
                            round(score_speedup, 3)
                            if score_speedup is not None else None
                        ),
                        pipeline_speedup=(
                            round(pipeline_speedup, 3)
                            if pipeline_speedup is not None else None
                        ),
                        batch_mean=round(coalesce_batch_mean, 2),
                        device_idle_ms=round(device_idle_ms, 2),
                        window_ms=round(window_ms, 3),
                        launch_overlaps=overlaps,
                    )
                finally:
                    if serial_server is not None:
                        serial_server.stop()
                    if coal_server is not None:
                        coal_server.stop()

                # compile truth probe (ISSUE 19): the same pipelined
                # storm with the launch ledger attributing compiles —
                # replies must stay byte-identical with the ledger-off
                # storm, the client-observed p99 must hold within the
                # overhead bound, and the ledger's own summary
                # publishes the compile costs the artifact carries
                # (device time: null, the ledger samples none).
                devprof_backend = devprof_compiles = None
                devprof_compile_ms_total = None
                devprof_device_score_us = None
                devprof_flops_per_launch = None
                devprof_overhead_pct = None
                from koordinator_tpu.obs import devprof

                try:
                    devprof.reset()
                    devprof.configure(
                        attribute=True,
                        metrics=server.servicer.telemetry.metrics,
                        state_dir=tmp,
                    )
                    # warm-up: pays the boundary AOT captures so the
                    # timed storms below measure steady-state wrapper
                    # overhead, not first-compile capture
                    _, _, dig_warm, errs = _score_storm(
                        sock_path, sync.snapshot_id, min(conc, 8), 1
                    )
                    assert not errs, f"devprof warm-up errors: {errs}"

                    def _p99(lat):
                        return lat[min(len(lat) - 1,
                                       int(round(0.99 * (len(lat) - 1))))]

                    # interleaved min-of-k (the ISSUE-14 trace-overhead
                    # idiom): alternate attribution on/off so scheduler
                    # noise hits both modes, keep each mode's
                    # least-perturbed p99.  per_client=1: the overhead
                    # delta needs matched storms, not a long soak.
                    reps = max(1, int(
                        os.environ.get("KOORD_DEVPROF_OVERHEAD_REPS")
                        or "3"
                    ))
                    p99_on_runs, p99_off_runs = [], []
                    dig_on = None
                    for _rep in range(reps):
                        _, lat_on, dig_on, errs = _score_storm(
                            sock_path, sync.snapshot_id, conc, 1
                        )
                        assert not errs, f"devprof-on storm errors: {errs}"
                        p99_on_runs.append(_p99(lat_on))
                        devprof.configure(attribute=False)
                        try:
                            _, lat_off, dig_off, errs = _score_storm(
                                sock_path, sync.snapshot_id, conc, 1
                            )
                        finally:
                            devprof.configure(attribute=True)
                        assert not errs, f"devprof-off storm errors: {errs}"
                        p99_off_runs.append(_p99(lat_off))
                        assert dig_off == dig_coal, (
                            "ledger-off storm replies diverged"
                        )
                    # reply-byte parity: the ledger may count, never
                    # touch a reply
                    assert dig_warm == dig_on == dig_coal, (
                        "devprof-on replies diverged from the "
                        "ledger-off storm"
                    )
                    p99_off_best = min(p99_off_runs)
                    devprof_overhead_pct = (
                        (min(p99_on_runs) - p99_off_best)
                        / p99_off_best * 100.0
                    )
                    summ = devprof.summary()
                    devprof_backend = summ["backend"]
                    ents = [e for e in summ["entries"]
                            if e["compile_ms"] is not None]
                    devprof_compiles = len(ents)
                    devprof_compile_ms_total = sum(
                        e["compile_ms"] for e in ents
                    )
                    flops = [e["flops"] for e in summ["entries"]
                             if e.get("flops")]
                    if flops:
                        devprof_flops_per_launch = max(flops)
                    phase(
                        "devprof_storm",
                        overhead_p99_pct=round(devprof_overhead_pct, 2),
                        compiles=devprof_compiles,
                        compile_ms_total=round(devprof_compile_ms_total, 2),
                        device_score_us=(
                            round(devprof_device_score_us, 1)
                            if devprof_device_score_us is not None
                            else None
                        ),
                        backend=devprof_backend,
                    )
                    # the acceptance bound (≤2% by default, overridable
                    # for noisy shared hosts).  A breach is recorded
                    # loudly but does NOT kill the leg: the measured
                    # overhead rides the artifact either way, and
                    # artifact-first is the whole point of this bench
                    # (the rc=124-no-artifact class) — on a contended
                    # 1-core container the p99 noise floor alone can
                    # exceed 2% of a multi-second storm.
                    max_pct = float(
                        os.environ.get("KOORD_DEVPROF_OVERHEAD_MAX_PCT")
                        or "2.0"
                    )
                    if devprof_overhead_pct > max_pct:
                        phase(
                            "devprof_overhead_breach",
                            overhead_p99_pct=round(devprof_overhead_pct, 2),
                            bound_pct=max_pct,
                            p99_off_ms=round(p99_off_best, 2),
                            p99_on_ms=round(min(p99_on_runs), 2),
                        )
                finally:
                    # process-global ledger: back to bit-inert before
                    # anything else touches the serving path
                    devprof.configure(attribute=False)
                    devprof.reset()
            finally:
                conn.close()
                server.stop()
        cold_ms = min(cold_times)
        warm_ms = min(warm_times)
        print(
            json.dumps(
                {
                    "metric": "bridge_assign_10kpod_2knode_ms",
                    # the cold steady-state price: Assign after a full
                    # Sync dropped residency (host re-encode + full
                    # upload + device cycle) — what every warm cycle
                    # paid before the resident fast path
                    "value": round(cold_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "path": reply.path,
                    "assigned": assigned,
                    # warm cycle: the delta sync scattered on device and
                    # Assign ran straight off the resident tensors
                    "warm_assign_ms": round(warm_ms, 2),
                    "warm_speedup": round(cold_ms / warm_ms, 3),
                    # incremental score engine (ISSUE 9): the warm
                    # SCORE cost — dirty-column rescore of the resident
                    # [P, N] tensor vs the full-rescore oracle (digest-
                    # identical by assertion), <=64 dirty nodes per
                    # delta; null = the probe failed and measured
                    # nothing
                    "warm_score_ms": (
                        round(warm_score_ms, 2)
                        if warm_score_ms is not None else None
                    ),
                    "full_warm_score_ms": (
                        round(full_warm_score_ms, 2)
                        if full_warm_score_ms is not None else None
                    ),
                    "incr_score_speedup": (
                        round(incr_score_speedup, 3)
                        if incr_score_speedup is not None else None
                    ),
                    "incr_cols_rescored": (
                        round(incr_cols_rescored, 1)
                        if incr_cols_rescored is not None else None
                    ),
                    "sync_ms": round(sync_ms, 1),
                    "sync_bytes": len(payload),
                    "delta_sync_ms": round(delta_sync_ms, 2),
                    "delta_sync_bytes": len(warm_payload),
                    "score_top32_ms": round(score_ms, 1),
                    "score_build_ms": round(score.build_ms, 2),
                    # coalesced-dispatch probe (ISSUE 5/6): aggregate
                    # Score throughput of N concurrent clients vs the
                    # serialized-lock baseline (max_batch=1/depth=1)
                    # and vs the ISSUE-5 depth-1 coalescer, with the
                    # mean batch occupancy the dispatcher achieved,
                    # the client-observed latency quantiles, and the
                    # pipeline-health numbers (device idle while work
                    # was queued ~ 0, the live adaptive window, and
                    # how many launches overlapped an in-flight batch)
                    "concurrency": conc,
                    # serialized-baseline sample size: < concurrency *
                    # reps means score_serial_wall_ms was measured on
                    # this many requests and extrapolated linearly
                    # (cpu-only; the serial engine is one-at-a-time so
                    # wall is linear in request count)
                    "score_serial_sample": serial_n,
                    "coalesce_batch_mean": round(coalesce_batch_mean, 2),
                    "p50_score_ms": round(p50, 2),
                    "p99_score_ms": round(p99, 2),
                    "score_serial_wall_ms": round(wall_serial * 1000.0, 1),
                    "score_depth1_wall_ms": round(wall_d1 * 1000.0, 1),
                    "score_coalesced_wall_ms": round(wall_coal * 1000.0, 1),
                    "score_concurrent_speedup": (
                        round(score_speedup, 3)
                        if score_speedup is not None else None
                    ),
                    "score_pipeline_speedup": (
                        round(pipeline_speedup, 3)
                        if pipeline_speedup is not None else None
                    ),
                    "device_idle_ms": round(device_idle_ms, 2),
                    "coalesce_window_ms": round(window_ms, 3),
                    "launch_overlaps": overlaps,
                    # device-time truth (ISSUE 19): the launch
                    # ledger's compile-vs-device split measured on the
                    # pipelined storm, plus the sampling p99 overhead
                    # vs the ledger-off storm (interleaved min-of-k)
                    "devprof_backend": devprof_backend,
                    "devprof_compiles": devprof_compiles,
                    "devprof_compile_ms_total": (
                        round(devprof_compile_ms_total, 2)
                        if devprof_compile_ms_total is not None else None
                    ),
                    "devprof_device_score_us": (
                        round(devprof_device_score_us, 1)
                        if devprof_device_score_us is not None else None
                    ),
                    "devprof_flops_per_launch": devprof_flops_per_launch,
                    "devprof_overhead_p99_pct": (
                        round(devprof_overhead_pct, 3)
                        if devprof_overhead_pct is not None else None
                    ),
                    # the warm-cycle stage breakdown a scraper of the
                    # daemon's /metrics histogram sees, artifact-side
                    "spans": {
                        "sync": round(sync_ms, 2),
                        "delta_sync": round(delta_sync_ms, 2),
                        "warm_assign": round(warm_ms, 2),
                        "cold_assign": round(cold_ms, 2),
                        "score_top32": round(score_ms, 2),
                        "warm_score_incr": (
                            round(warm_score_ms, 2)
                            if warm_score_ms is not None else None
                        ),
                        "warm_score_full": (
                            round(full_warm_score_ms, 2)
                            if full_warm_score_ms is not None else None
                        ),
                        "score_storm_serial": round(wall_serial * 1000.0, 2),
                        "score_storm_depth1": round(wall_d1 * 1000.0, 2),
                        "score_storm_coalesced": round(wall_coal * 1000.0, 2),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "mesh":
        # ISSUE 7 scale point: the MESH-SHARDED resident snapshot — one
        # cluster spread over every visible device (node tensors split
        # along the cluster axis, pod/quota rows replicated), warm delta
        # Syncs landing as shard-local scatters, Assign running the
        # round-based multi-chip cycle, bit-identical to the single-chip
        # oracle.  Scale: 100k x 10k where memory permits, else halved
        # to the largest size fitting KOORD_BENCH_MESH_BYTES (pad
        # buckets round up to powers of two, so the mesh always
        # divides).  CPU rounds (8 forced-host devices) measure the
        # shard-local Sync cost and the capacity math; like the
        # pipeline probe, the collective/compute overlap the mesh buys
        # needs real ICI, so mesh_speedup < 1 on CPU is expected.
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.config import CycleConfig
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.parallel import cluster_mesh, pow2_device_count

        devices = jax.devices()
        # round down to a power-of-two prefix (the daemon's --mesh rule):
        # node buckets are powers of two, so a 6-device mesh would never
        # activate and the config would silently measure single-chip
        # vs single-chip while claiming mesh_devices=6
        mesh = cluster_mesh(devices[: pow2_device_count(len(devices))])
        budget_bytes = float(
            os.environ.get("KOORD_BENCH_MESH_BYTES", 128 * 1024 * 1024)
        )
        mesh_pods, mesh_nodes = 100_000, 10_000
        # ~4 [P, N]-sized i64 intermediates dominate the Score footprint
        while mesh_pods * mesh_nodes * 32 > budget_bytes and mesh_nodes > 256:
            mesh_pods //= 2
            mesh_nodes //= 2
        mesh_pods = int(os.environ.get("KOORD_BENCH_MESH_PODS", mesh_pods))
        mesh_nodes = int(os.environ.get("KOORD_BENCH_MESH_NODES", mesh_nodes))
        phase(
            "scale", pods=mesh_pods, nodes=mesh_nodes,
            mesh_devices=mesh.size,
        )
        _, nodes, pods, gangs, quotas, _ = generators.quota_colocation_snapshot(
            pods=mesh_pods, nodes=mesh_nodes
        )
        # buckets omitted: the resident state pads to powers of two, so
        # the node axis always divides over a power-of-two mesh
        req, _ = build_sync_request(nodes, pods, gangs, quotas)
        payload = req.SerializeToString()
        cfg = CycleConfig()

        def drive(sv, label):
            """Full Sync -> cold Assign -> 3 warm delta-Sync/Assign
            reps; returns (sync_ms, min delta ms, min warm assign ms,
            final reply)."""
            t0 = time.perf_counter()
            sync = sv.sync(pb2.SyncRequest.FromString(payload))
            sync_ms = _ms(t0)
            reply = sv.assign(pb2.AssignRequest(snapshot_id=sync.snapshot_id))
            phase(f"{label}_first_assign", path=reply.path)
            prev = np.asarray(
                [list(map(int, res.resource_vector(n.get("usage", {}))))
                 for n in nodes], dtype=np.int64,
            )
            delta_times, warm_times = [], []
            for rep in range(3):
                cur = prev.copy()
                cur[:3, 0] += 500 + rep
                warm = pb2.SyncRequest()
                warm.nodes.usage.CopyFrom(numpy_to_tensor(cur, prev))
                t0 = time.perf_counter()
                sync = sv.sync(warm)
                delta_times.append(_ms(t0))
                prev = cur
                assert sv.state.last_sync_path == "warm", (
                    f"{label}: delta sync must land on the resident tensors"
                )
                t0 = time.perf_counter()
                reply = sv.assign(
                    pb2.AssignRequest(snapshot_id=sync.snapshot_id)
                )
                warm_times.append(_ms(t0))
            phase(
                f"{label}_warm",
                assign_ms=round(min(warm_times), 2),
                delta_sync_ms=round(min(delta_times), 2),
            )
            return sync_ms, min(delta_times), min(warm_times), reply

        single = ScorerServicer(cfg, score_memo=False)
        s_sync_ms, s_delta_ms, s_assign_ms, s_reply = drive(single, "single")
        meshed = ScorerServicer(
            cfg, mesh=mesh, mesh_resident=True, score_memo=False
        )
        m_sync_ms, m_delta_ms, m_assign_ms, m_reply = drive(meshed, "mesh")
        # the acceptance bit: mesh-sharded placements == single-chip
        assert list(m_reply.assignment) == list(s_reply.assignment), (
            "mesh-sharded cycle diverged from the single-chip oracle"
        )
        assert list(m_reply.status) == list(s_reply.status)

        # capacity math: resident bytes one device must hold, sharded vs
        # replicated-on-one-chip, plus the transient [P, N] Score-tensor
        # footprint the node sharding divides by the mesh (the 100k x
        # 10k fp32 cost tensor is the ~4 GB that forces this refactor;
        # docs/KERNEL.md "Mesh sharding" carries the budget table)
        snap = meshed.state.snapshot()
        total = 0
        per_device = 0
        for leaf in jax.tree_util.tree_leaves(snap):
            nbytes = leaf.size * leaf.dtype.itemsize
            total += nbytes
            # single-chip fallback placements (indivisible bucket) carry
            # a SingleDeviceSharding with no .spec — count them whole
            spec = getattr(leaf.sharding, "spec", None) or ()
            sharded = any(s is not None for s in spec)
            per_device += nbytes // mesh.size if sharded else nbytes
        score_mb = mesh_pods * mesh_nodes * 8 / 1e6
        print(
            json.dumps(
                {
                    "metric": "mesh_sharded_assign_ms",
                    "value": round(m_assign_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "pods": mesh_pods,
                    "nodes": mesh_nodes,
                    "path": m_reply.path,
                    "mesh_devices": mesh.size,
                    # warm delta Sync against the SHARDED snapshot: the
                    # scatter lands on the owning shard only, so this
                    # stays flat as the mesh grows
                    "shard_sync_ms": round(m_delta_ms, 2),
                    "mesh_assign_ms": round(m_assign_ms, 2),
                    "mesh_speedup": round(s_assign_ms / m_assign_ms, 3)
                    if m_assign_ms > 0 else None,
                    "single_assign_ms": round(s_assign_ms, 2),
                    "single_sync_ms": round(s_delta_ms, 2),
                    "resident_mb_total": round(total / 1e6, 2),
                    "resident_mb_per_device": round(per_device / 1e6, 2),
                    # transient Score-tensor footprint per device: the
                    # node axis divides it by the mesh — the >= 4x
                    # single-chip-capacity multiplier at >= 4 devices
                    "score_tensor_mb": round(score_mb, 1),
                    "score_tensor_mb_per_device": round(
                        score_mb / mesh.size, 1
                    ),
                    "spans": {
                        "single_sync": round(s_sync_ms, 2),
                        "single_delta_sync": round(s_delta_ms, 2),
                        "single_assign": round(s_assign_ms, 2),
                        "mesh_sync": round(m_sync_ms, 2),
                        "mesh_delta_sync": round(m_delta_ms, 2),
                        "mesh_assign": round(m_assign_ms, 2),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "replica":
        _one_process_rule(backend, config)
        # ISSUE 8 scale point: the REPLICATED SERVING TIER — one leader
        # daemon streaming committed Syncs to M follower daemons (real
        # subprocesses: real per-replica jax runtimes, the scaling the
        # tier exists to buy), M x N clients storming the followers,
        # digest-identical to the single-daemon oracle, plus the
        # admission-gate overload leg (shed_rate).  CPU rounds measure
        # process-parallel read scaling of the same launches.
        import subprocess as sp
        import tempfile

        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.server import ScorerServicer
        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.bridge.udsserver import RawUdsServer
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.replication.leader import ReplicationPublisher

        # Scale: the replica tier exists to multiply READ throughput,
        # and the quantity it multiplies is per-daemon serving capacity
        # (dispatch, demux, reply assembly — the Python the GIL
        # serializes) plus whatever device time a launch costs.  The
        # default scale keeps the per-launch tensor small enough that
        # one daemon's serving loop — not this host's core count — is
        # the oracle's bottleneck, which is exactly the regime the
        # tier targets (on real deployments each replica owns its own
        # chip, so launch compute scales with the tier as well).
        r_pods = int(os.environ.get("KOORD_BENCH_REPLICA_PODS", "256"))
        r_nodes = int(os.environ.get("KOORD_BENCH_REPLICA_NODES", "64"))
        # optional gather-cap override applied to EVERY daemon (leader
        # and followers alike — same knob, both legs, so the legs
        # differ only in how many daemons serve them); empty = the
        # daemon's default adaptive window
        r_cap_env = os.environ.get("KOORD_BENCH_REPLICA_CAP_MS", "")
        r_cap_ms = float(r_cap_env) if r_cap_env else None
        followers_n = int(
            os.environ.get("KOORD_BENCH_REPLICA_FOLLOWERS", "3")
        )
        clients_per = int(
            os.environ.get("KOORD_BENCH_REPLICA_CLIENTS", "16")
        )
        reps = int(os.environ.get("KOORD_BENCH_REPLICA_REPS", "3"))
        total_clients = followers_n * clients_per
        nodes, pods_l, gangs, quotas = generators.quota_colocation(
            pods=r_pods, nodes=r_nodes
        )
        req, _ = build_sync_request(nodes, pods_l, gangs, quotas)
        payload = req.SerializeToString()
        phase(
            "scale", pods=r_pods, nodes=r_nodes,
            followers=followers_n, clients=total_clients, reps=reps,
        )
        with tempfile.TemporaryDirectory() as tmp:
            # the follower processes share this process's persistent
            # compile cache (the package's one rule): the leader
            # compiles, the followers deserialize
            leader_sock = os.path.join(tmp, "leader.sock")
            repl_sock = os.path.join(tmp, "leader.repl")
            # memo AND incremental engine off (the --config bridge storm
            # rule): the replica storms fire Scores at one unchanged
            # snapshot, and the engine's empty-dirty-set passthrough
            # would skip the scoring math the tier's read scaling is
            # supposed to amortize — replica_read_speedup must keep
            # PR 8's meaning
            leader_sv = ScorerServicer(
                score_memo=False, score_incr=False,
                **({} if r_cap_ms is None
                   else {"coalesce_cap_ms": r_cap_ms}),
            )
            leader_srv = RawUdsServer(leader_sock, servicer=leader_sv)
            leader_srv.start()
            pub = ReplicationPublisher(leader_sv, repl_sock)
            pub.attach().start()
            procs = []
            try:
                sid = leader_sv.sync(req).snapshot_id
                phase("sync", snapshot_id=sid, bytes=len(payload))

                env = dict(os.environ)

                def run_storm(socks, label):
                    """M CLIENT PROCESSES of N workers each (real
                    clients: a single bench-process GIL would pace the
                    arrivals and starve every coalescer it storms),
                    identical for both legs — the only variable is
                    which daemon(s) the sockets name.  Workers warm up,
                    signal STORM_READY, and fire together on GO; the
                    wall is the slowest process's storm wall."""
                    storm_procs = []
                    for sock in socks:
                        storm_procs.append(sp.Popen(
                            [
                                sys.executable,
                                os.path.abspath(__file__),
                                "--replica-storm",
                                "--platform", platform,
                                "--storm-sock", sock,
                                "--storm-clients", str(clients_per),
                                "--storm-reps", str(reps),
                                "--storm-snapshot", sid,
                            ],
                            env=env, stdin=sp.PIPE, stdout=sp.PIPE,
                            text=True,
                            cwd=os.path.dirname(
                                os.path.abspath(__file__)
                            ),
                        ))
                    try:
                        for p in storm_procs:
                            line = p.stdout.readline()
                            while line and line.strip() != "STORM_READY":
                                line = p.stdout.readline()
                            assert line, (
                                f"{label} storm worker died before READY"
                            )
                        for p in storm_procs:
                            p.stdin.write("GO\n")
                            p.stdin.flush()
                        results = []
                        for p in storm_procs:
                            out = p.stdout.readline()
                            assert out, f"{label} storm worker died"
                            results.append(json.loads(out))
                    finally:
                        for p in storm_procs:
                            try:
                                p.stdin.close()
                            except OSError:
                                pass
                            try:
                                p.wait(timeout=60)
                            except sp.TimeoutExpired:
                                p.kill()
                    errs = sum((r["errors"] for r in results), [])
                    digs = set()
                    for r in results:
                        digs.update(r["digests"])
                    return max(r["storm_wall_s"] for r in results), \
                        digs, errs

                # follower daemons: separate PROCESSES subscribed to
                # the leader's replication socket (stdout swallowed —
                # only the bench child may print artifact lines)
                follower_socks, status_files = [], []
                if r_cap_ms is not None:
                    env["KOORD_COALESCE_CAP_MS"] = str(r_cap_ms)
                for i in range(followers_n):
                    fsock = os.path.join(tmp, f"f{i}.sock")
                    sfile = os.path.join(tmp, f"f{i}.status.json")
                    follower_socks.append(fsock)
                    status_files.append(sfile)
                    procs.append(sp.Popen(
                        [
                            sys.executable, os.path.abspath(__file__),
                            "--replica-follower",
                            "--platform", platform,
                            "--follower-sock", fsock,
                            "--replicate-from", repl_sock,
                            "--status-file", sfile,
                        ],
                        env=env, stdout=sp.DEVNULL,
                        cwd=os.path.dirname(os.path.abspath(__file__)),
                    ))

                def follower_status(i):
                    try:
                        with open(status_files[i]) as fh:
                            return json.load(fh)
                    except (OSError, ValueError):
                        return {}

                def caught_up(want_sid):
                    return all(
                        follower_status(i).get("snapshot_id") == want_sid
                        for i in range(followers_n)
                    )

                def wait_caught_up(want_sid, timeout_s):
                    deadline = time.monotonic() + timeout_s
                    while time.monotonic() < deadline:
                        if caught_up(want_sid):
                            return True
                        for p in procs:
                            assert p.poll() is None, (
                                "follower process died before catch-up"
                            )
                        time.sleep(0.1)
                    return caught_up(want_sid)

                assert wait_caught_up(sid, float(
                    os.environ.get("KOORD_BENCH_REPLICA_WAIT", "240")
                )), "followers failed to catch up with the leader"
                phase("followers_ready", followers=followers_n)

                # single-daemon ORACLE: all M x N clients (M client
                # processes) on the one leader — the deployment the
                # tier replaces
                wall_single, dig_single, errs = run_storm(
                    [leader_sock] * followers_n, "oracle"
                )
                assert not errs, f"oracle storm errors: {errs}"
                assert len(dig_single) == 1
                phase(
                    "oracle_storm",
                    wall_ms=round(wall_single * 1000.0, 1),
                    clients=total_clients,
                )

                # REPLICA TIER storm: the SAME M x N clients, process
                # i's N workers on follower i
                wall_tier, dig_tier, errs = run_storm(
                    follower_socks, "tier"
                )
                assert not errs, f"tier storm errors: {errs}"
                # the acceptance bit: every follower reply is
                # byte-identical to the single-daemon oracle's
                assert dig_tier == dig_single, (
                    "replica tier replies diverged from the "
                    "single-daemon oracle"
                )
                speedup = (
                    wall_single / wall_tier if wall_tier > 0 else None
                )
                phase(
                    "tier_storm",
                    wall_ms=round(wall_tier * 1000.0, 1),
                    speedup=(
                        round(speedup, 3) if speedup is not None
                        else None
                    ),
                )

                # warm delta frames -> replication lag: three sparse
                # usage deltas ride the stream at wire size; the lag
                # gauge is commit-to-apply wall time on the follower
                prev = np.asarray(
                    [res.resource_vector(n.get("usage", {}))
                     for n in nodes],
                    dtype=np.int64,
                )
                delta_bytes = 0
                for rep in range(3):
                    cur = prev.copy()
                    cur[:3, 0] += 100 + rep
                    warm = pb2.SyncRequest()
                    warm.nodes.usage.CopyFrom(numpy_to_tensor(cur, prev))
                    delta_bytes = len(warm.SerializeToString())
                    sid = leader_sv.sync(warm).snapshot_id
                    prev = cur
                assert wait_caught_up(sid, 60.0), (
                    "followers failed to apply the warm delta frames"
                )
                lags = [
                    follower_status(i).get("lag_ms")
                    for i in range(followers_n)
                ]
                lags = [
                    float(l) for l in lags if isinstance(l, (int, float))
                ]
                replica_lag_ms = max(lags) if lags else None
                phase(
                    "replica_lag",
                    lag_ms=(
                        round(replica_lag_ms, 2)
                        if replica_lag_ms is not None else None
                    ),
                    delta_frame_bytes=delta_bytes,
                )
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except sp.TimeoutExpired:
                        p.kill()
                pub.stop()
                leader_srv.stop()

            # ADMISSION overload leg: a gated daemon under a one-shot
            # burst far past --max-inflight — excess sheds fast with
            # RESOURCE_EXHAUSTED while admitted work completes and
            # stays byte-identical to the oracle
            shed_clients = int(
                os.environ.get("KOORD_BENCH_SHED_CLIENTS", "32")
            )
            max_inflight = int(
                os.environ.get("KOORD_BENCH_SHED_INFLIGHT", "2")
            )
            # incremental engine off too: the passthrough would collapse
            # service time and a burst could drain below --max-inflight
            # before it sheds, failing the shed>0 acceptance spuriously
            gated_sv = ScorerServicer(
                score_memo=False, score_incr=False,
                max_inflight=max_inflight,
            )
            gated_srv = RawUdsServer(
                os.path.join(tmp, "gated.sock"), servicer=gated_sv
            )
            gated_srv.start()
            try:
                gsid = gated_sv.sync(
                    pb2.SyncRequest.FromString(payload)
                ).snapshot_id
                # one untimed call: compile + cold snapshot build must
                # not ride the overload measurement
                gated_sv.score(pb2.ScoreRequest(
                    snapshot_id=gsid, top_k=32, flat=True
                ))
                served, shed, other, max_shed_ms = _shed_storm(
                    gated_srv.path, gsid, clients=shed_clients
                )
                assert not other, f"shed storm errors: {other}"
                assert shed > 0, (
                    "a burst far past --max-inflight must shed"
                )
                assert served, "admitted work must complete untouched"
                assert served <= dig_single, (
                    "served replies diverged under overload"
                )
                shed_rate = shed / float(shed_clients)
                phase(
                    "shed",
                    shed=shed,
                    clients=shed_clients,
                    max_inflight=max_inflight,
                    shed_rate=round(shed_rate, 3),
                    max_shed_reply_ms=round(max_shed_ms, 2),
                )
            finally:
                gated_srv.stop()
        # the CPU caveat, stated in the artifact like the mesh config's
        # (mesh_speedup < 1 on the host backend is expected and
        # documented): every replica daemon AND every client process
        # shares this host's cores, so a box with fewer than
        # ~(followers + 1) cores physically cannot show the tier's
        # read scaling — the single-daemon oracle already saturates
        # the same silicon the followers would use.  On deployments
        # the tier targets, each replica owns its own host/chip.
        cpu_count = os.cpu_count() or 1
        note = None
        if backend == "cpu" and cpu_count < followers_n + 1:
            note = (
                f"host has {cpu_count} cores for {followers_n} replica "
                "processes + clients: replica_read_speedup is "
                "core-starved here; the tier's scaling needs one "
                "host/chip per replica (see docs/REPLICATION.md)"
            )
        print(
            json.dumps(
                {
                    "metric": "replica_tier_score_wall_ms",
                    # the headline: the M x N-client storm wall on the
                    # follower tier (the single-daemon oracle wall and
                    # the ratio ride alongside)
                    "value": round(wall_tier * 1000.0, 2),
                    "unit": "ms",
                    "backend": backend,
                    "pods": r_pods,
                    "nodes": r_nodes,
                    "cpu_count": cpu_count,
                    **({} if note is None else {"note": note}),
                    "concurrency": total_clients,
                    "replica_count": followers_n,
                    "single_wall_ms": round(wall_single * 1000.0, 2),
                    "replica_read_speedup": (
                        round(speedup, 3) if speedup is not None else None
                    ),
                    "replica_lag_ms": (
                        round(replica_lag_ms, 2)
                        if replica_lag_ms is not None else None
                    ),
                    "shed_rate": round(shed_rate, 3),
                    "max_shed_reply_ms": round(max_shed_ms, 2),
                    "delta_frame_bytes": delta_bytes,
                    "spans": {
                        "oracle_storm": round(wall_single * 1000.0, 2),
                        "tier_storm": round(wall_tier * 1000.0, 2),
                        "replica_lag": (
                            round(replica_lag_ms, 2)
                            if replica_lag_ms is not None else None
                        ),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "tree":
        # ISSUE 18: the CHAINABLE FOLLOWER RELAY TREE + elastic tier.
        # Four legs over one in-process tier of real SchedulerServer
        # daemons (root leader -> depth-3 relay chain, plus one flat
        # follower of the root for the comparison): (1) a delta storm
        # converging through every hop — the headline wall — with
        # reply-byte parity asserted leaf vs root vs flat follower and
        # the fan-out amplification read off the real publisher
        # counters; (2) the chaos leg: an INTERIOR relay dies
        # mid-storm and its descendants must resume through a
        # surviving ancestor's hello/resume splice with ZERO full
        # opens and ZERO applier resyncs; (3) a read storm served by
        # the tree's leaves vs the same storm on one flat follower
        # (tree_read_speedup, core-starved on this container — the
        # honest note below); (4) the autoscale wave: a real
        # ReplicaAutoscaler holding a declared read p99 through a 10x
        # traffic wave, its spawn/drain levers wired to REAL leaf
        # daemons spliced into the tree.
        import tempfile
        import threading as _threading

        import koordinator_tpu.obs  # noqa: F401  (before replication: import cycle)
        from koordinator_tpu.harness.chaos import flat_score_bytes
        from koordinator_tpu.harness.golden import build_sync_request
        from koordinator_tpu.harness.relay import (
            RelayTier,
            autoscale_wave,
            wait_until,
        )

        t_pods = int(os.environ.get("KOORD_BENCH_TREE_PODS", "192"))
        t_nodes = int(os.environ.get("KOORD_BENCH_TREE_NODES", "48"))
        t_deltas = int(os.environ.get("KOORD_BENCH_TREE_DELTAS", "10"))
        t_depth = 3

        def _tree_sync(seed):
            nodes_l, pods_l, gangs, quotas = generators.quota_colocation(
                seed=seed, pods=t_pods, nodes=t_nodes, tenants=4
            )
            req, _ = build_sync_request(nodes_l, pods_l, gangs, quotas)
            return req

        phase("scale", pods=t_pods, nodes=t_nodes, deltas=t_deltas,
              depth=t_depth)
        with tempfile.TemporaryDirectory() as tmp:
            tier = RelayTier(
                tmp, chain=t_depth, flat=1, compress=True,
                batch_bytes=64 * 1024,
            )
            try:
                # cold converge (compile + full-frame opens), untimed —
                # generous window: on a cold compile cache every daemon
                # jits the apply path serially on this host's cores
                sid = tier.sync(_tree_sync(0))
                assert tier.wait(sid, timeout_s=240.0), (
                    "cold converge timed out"
                )
                phase("converged", snapshot_id=sid,
                      followers=len(tier.followers()))

                # -- leg 1: the delta storm through every hop --------
                t0 = time.perf_counter()
                for s in range(1, t_deltas + 1):
                    sid = tier.sync(_tree_sync(s))
                assert tier.wait(sid, timeout_s=120.0), (
                    "delta storm never converged"
                )
                converge_wall_ms = _ms(t0)
                root_sv = tier.leader.servicer
                leaf_sv = tier.chain[-1].servicer
                flat_sv = tier.flat[0].servicer
                want = flat_score_bytes(root_sv, sid)
                assert flat_score_bytes(leaf_sv, sid) == want, (
                    "depth-3 leaf reply bytes diverged from the root"
                )
                assert flat_score_bytes(flat_sv, sid) == want, (
                    "flat follower reply bytes diverged from the root"
                )
                root_stats = tier.leader._publisher.stats()
                relay_sent = sum(
                    s._publisher.stats()["sent_frames"]
                    for s in tier.followers()
                    if getattr(s, "_publisher", None) is not None
                )
                total_sent = root_stats["sent_frames"] + relay_sent
                fanout_amp = (
                    total_sent / root_stats["sent_frames"]
                    if root_stats["sent_frames"] else 0.0
                )
                phase("storm", wall_ms=round(converge_wall_ms, 2),
                      root_sent=root_stats["sent_frames"],
                      total_sent=total_sent,
                      fanout_amplification=round(fanout_amp, 3))

                # -- leg 2: interior-relay death mid-storm -----------
                victim = tier.chain[1]

                def _opens(skip=None):
                    total = 0
                    for srv in [tier.leader] + tier.followers():
                        if srv is skip:
                            continue
                        pub = getattr(srv, "_publisher", None)
                        if pub is not None:
                            total += (
                                pub.subscriptions
                                - pub.resumed_subscriptions
                            )
                    return total

                def _resyncs(skip=None):
                    return sum(
                        s.applier.resyncs
                        for s in tier.followers()
                        if s is not skip
                        and getattr(s, "applier", None) is not None
                    )

                opens0 = _opens(skip=victim)
                resyncs0 = _resyncs(skip=victim)
                for s in range(t_deltas + 1, t_deltas + 4):
                    sid = tier.sync(_tree_sync(s))
                tier.kill(1)  # the interior hop: descendants redial
                for s in range(t_deltas + 4, t_deltas + 7):
                    sid = tier.sync(_tree_sync(s))
                assert tier.wait(sid, timeout_s=120.0), (
                    "descendants never converged after the interior kill"
                )
                full_opens_failover = tier.full_opens() - opens0
                resyncs_failover = tier.resyncs() - resyncs0
                switches = sum(
                    getattr(s._subscriber, "ancestor_switches", 0)
                    for s in tier.followers()
                    if getattr(s, "_subscriber", None) is not None
                )
                assert resyncs_failover == 0, (
                    f"{resyncs_failover} full resyncs during interior "
                    "failover: the ancestor splice did not hold"
                )
                assert full_opens_failover == 0, (
                    f"{full_opens_failover} full-frame opens during "
                    "interior failover"
                )
                assert switches >= 1, "no descendant redialed an ancestor"
                assert flat_score_bytes(leaf_sv, sid) == flat_score_bytes(
                    root_sv, sid
                ), "leaf diverged after re-parenting"
                phase("chaos", resyncs=resyncs_failover,
                      full_opens=full_opens_failover,
                      ancestor_switches=switches)

                # -- leg 3: leaf read storm vs one flat follower -----
                extra_leaf = tier.spawn_leaf()
                assert wait_until(
                    lambda: extra_leaf.servicer.snapshot_id() == sid,
                    timeout_s=60.0,
                ), "elastic leaf never converged"
                storm_clients = int(
                    os.environ.get("KOORD_BENCH_TREE_CLIENTS", "8")
                )
                reps = int(os.environ.get("KOORD_BENCH_TREE_REPS", "2"))
                wall_flat, _, dig_flat, errs = _score_storm(
                    tier.flat[0].uds_path + ".raw", sid,
                    clients=storm_clients, per_client=reps,
                )
                assert not errs, f"flat storm errors: {errs}"
                leaves = [tier.chain[-1], extra_leaf]
                per_leaf = max(1, storm_clients // len(leaves))
                results = [None] * len(leaves)

                def _leaf_storm(i, srv):
                    results[i] = _score_storm(
                        srv.uds_path + ".raw", sid,
                        clients=per_leaf, per_client=reps,
                    )

                threads = [
                    _threading.Thread(target=_leaf_storm, args=(i, srv))
                    for i, srv in enumerate(leaves)
                ]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                wall_tree = time.perf_counter() - t0
                dig_tree = set()
                for res in results:
                    assert res is not None, "leaf storm never finished"
                    assert not res[3], f"leaf storm errors: {res[3]}"
                    dig_tree |= res[2]
                assert dig_tree == dig_flat, (
                    "tree-leaf replies diverged from the flat follower"
                )
                tree_read_speedup = (
                    wall_flat / wall_tree if wall_tree > 0 else None
                )
                phase("reads", flat_wall_ms=round(wall_flat * 1000, 2),
                      tree_wall_ms=round(wall_tree * 1000, 2),
                      speedup=round(tree_read_speedup, 3)
                      if tree_read_speedup else None)

                # -- leg 4: the autoscale wave over real leaves ------
                wave = autoscale_wave(
                    ticks=int(os.environ.get(
                        "KOORD_BENCH_TREE_WAVE_TICKS", "48"
                    )),
                    peak=10.0,
                    spawn=tier.spawn_leaf,
                    drain=tier.drain_leaf,
                )
                assert wave["scale_ups"] >= 1, (
                    "the 10x wave never scaled the tier up"
                )
                assert wave["slo_held"], (
                    "read p99 SLO lost on the plateau: "
                    f"{wave['plateau_ticks_within_slo']}/"
                    f"{wave['plateau_ticks_judged']} ticks in SLO"
                )
                phase("autoscale", scale_ups=wave["scale_ups"],
                      scale_downs=wave["scale_downs"],
                      peak_replicas=wave["peak_replicas"],
                      slo_held=wave["slo_held"],
                      spawn_to_ready_ms=wave["spawn_to_ready_ms"])

                compressed = sum(
                    s._publisher.stats()["compressed_fulls"]
                    for s in [tier.leader] + tier.followers()
                    if getattr(s, "_publisher", None) is not None
                )
                final_stats = tier.leader._publisher.stats()
            finally:
                tier.stop()

        # the CPU caveat, replica-config precedent: every daemon in the
        # tree AND the storm clients share this host's cores, so a box
        # with fewer cores than daemons cannot show the tree's read
        # scaling — tree_read_speedup here measures protocol overhead
        # parity, not fan-out capacity.  On the deployments the tree
        # targets each relay owns its own host.
        cpu_count = os.cpu_count() or 1
        note = None
        if backend == "cpu" and cpu_count < t_depth + 3:
            note = (
                f"host has {cpu_count} cores for a depth-{t_depth} tree "
                "of daemons + clients: tree_read_speedup is core-starved "
                "here; the tree's fan-out scaling needs one host per "
                "relay (see docs/REPLICATION.md)"
            )
        print(
            json.dumps(
                {
                    "metric": "tree_converge_wall_ms",
                    # the headline: the delta-storm wall from first
                    # publish to every follower converged through the
                    # depth-3 chain
                    "value": round(converge_wall_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "pods": t_pods,
                    "nodes": t_nodes,
                    "cpu_count": cpu_count,
                    **({} if note is None else {"note": note}),
                    "tree_depth": t_depth,
                    "tree_fanout_amplification": round(fanout_amp, 3),
                    "tree_read_speedup": (
                        round(tree_read_speedup, 3)
                        if tree_read_speedup is not None else None
                    ),
                    "resyncs_during_failover": resyncs_failover,
                    "full_opens_during_failover": full_opens_failover,
                    "ancestor_switches": switches,
                    "compressed_fulls": compressed,
                    "frames_per_wakeup": round(
                        final_stats["frames_per_wakeup"], 3
                    ),
                    "autoscale_scale_ups": wave["scale_ups"],
                    "autoscale_scale_downs": wave["scale_downs"],
                    "autoscale_peak_replicas": wave["peak_replicas"],
                    "autoscale_slo_held": wave["slo_held"],
                    # spawn -> serving economics of the tier's capacity
                    # lever (ISSUE 20): RelayTier.spawn_leaf returns
                    # once the leaf's server started, so this is real
                    "spawn_to_ready_ms": wave["spawn_to_ready_ms"],
                    "spans": {
                        "converge_storm": round(converge_wall_ms, 2),
                        "flat_read_storm": round(wall_flat * 1000, 2),
                        "tree_read_storm": round(wall_tree * 1000, 2),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "failover":
        _one_process_rule(backend, config)
        # ISSUE 11: crash-tolerant serving tier.  Kill the leader
        # subprocess with SIGKILL mid-read-storm and recover it BOTH
        # documented ways — (A) journal warm-restart onto the SAME
        # s<epoch>-<gen> chain, (B) follower promotion via SIGUSR2 —
        # publishing the recovery economics: failover_ms,
        # journal_replay_ms, journal_append_us, and how many follower
        # full-resyncs the whole storm cost (0 is the journal's win).
        import signal as _signal
        import socket as _socket
        import struct as _struct
        import subprocess as sp
        import tempfile

        from koordinator_tpu.bridge.client import parse_snapshot_id
        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.state import numpy_to_tensor
        from koordinator_tpu.bridge.udsserver import (
            METHOD_SCORE,
            METHOD_SYNC,
        )
        from koordinator_tpu.harness.golden import build_sync_request

        f_pods = int(os.environ.get("KOORD_BENCH_FAILOVER_PODS", "256"))
        f_nodes = int(os.environ.get("KOORD_BENCH_FAILOVER_NODES", "64"))
        f_deltas = int(
            os.environ.get("KOORD_BENCH_FAILOVER_DELTAS", "8")
        )
        wait_s = float(
            os.environ.get("KOORD_BENCH_FAILOVER_WAIT", "240")
        )
        nodes, pods_l, gangs, quotas = generators.quota_colocation(
            pods=f_pods, nodes=f_nodes
        )
        req, _ = build_sync_request(nodes, pods_l, gangs, quotas)
        payload = req.SerializeToString()
        phase("scale", pods=f_pods, nodes=f_nodes, deltas=f_deltas)
        with tempfile.TemporaryDirectory() as tmp:
            state_dir = os.path.join(tmp, "leader-state")
            leader_sock = os.path.join(tmp, "leader.sock")
            leader_repl = os.path.join(tmp, "leader.repl")
            lstatus = os.path.join(tmp, "leader.status.json")
            fsock = os.path.join(tmp, "f0.sock")
            frepl = os.path.join(tmp, "f0.repl")
            fstatus = os.path.join(tmp, "f0.status.json")
            fstate = os.path.join(tmp, "f0-state")
            env = dict(os.environ)

            def read_status(path):
                try:
                    with open(path) as fh:
                        return json.load(fh)
                except (OSError, ValueError):
                    return {}

            def wait_status(path, pred, timeout_s, what):
                deadline = time.monotonic() + timeout_s
                while time.monotonic() < deadline:
                    if pred(read_status(path)):
                        return read_status(path)
                    time.sleep(0.05)
                st = read_status(path)
                assert pred(st), f"timed out waiting for {what}: {st}"
                return st

            def spawn_leader():
                # the PREVIOUS leader's status file must not satisfy a
                # wait meant for the new one (the socket would not be
                # bound yet): the status a wait sees must come from the
                # process it waits for
                try:
                    os.unlink(lstatus)
                except OSError:
                    pass
                return sp.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--failover-leader",
                        "--platform", platform,
                        "--leader-sock", leader_sock,
                        "--leader-repl", leader_repl,
                        "--leader-state-dir", state_dir,
                        "--status-file", lstatus,
                    ],
                    env=env, stdout=sp.DEVNULL,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )

            def raw_call(sock_path, method, body, timeout=60.0):
                conn = _socket.socket(
                    _socket.AF_UNIX, _socket.SOCK_STREAM
                )
                conn.settimeout(timeout)
                try:
                    conn.connect(sock_path)
                    conn.sendall(
                        _struct.pack(">BI", method, len(body)) + body
                    )
                    hdr = b""
                    while len(hdr) < 5:
                        chunk = conn.recv(5 - len(hdr))
                        if not chunk:
                            raise ConnectionError("closed mid-reply")
                        hdr += chunk
                    status, ln = _struct.unpack(">BI", hdr)
                    out = b""
                    while len(out) < ln:
                        chunk = conn.recv(ln - len(out))
                        if not chunk:
                            raise ConnectionError("closed mid-reply")
                        out += chunk
                    return status, out
                finally:
                    conn.close()

            def raw_sync(sock_path, body):
                status, out = raw_call(sock_path, METHOD_SYNC, body)
                assert status == 0, out[:200]
                return pb2.SyncReply.FromString(out)

            leader = spawn_leader()
            procs = [leader]
            storm_stop = threading.Event()
            storm_threads = []
            reads_lock = threading.Lock()
            reads = {"ok": 0, "err": 0, "ok_during_failover": 0}
            in_failover = threading.Event()
            try:
                wait_status(
                    lstatus, lambda s: s.get("snapshot_id"), wait_s,
                    "leader boot",
                )
                sid = raw_sync(leader_sock, payload).snapshot_id
                phase("sync", snapshot_id=sid, bytes=len(payload))
                procs.append(sp.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--replica-follower",
                        "--platform", platform,
                        "--follower-sock", fsock,
                        "--replicate-from", leader_repl,
                        "--status-file", fstatus,
                        "--promote-repl", frepl,
                        "--promote-state-dir", fstate,
                    ],
                    env=env, stdout=sp.DEVNULL,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                ))
                follower = procs[-1]
                wait_status(
                    fstatus,
                    lambda s: s.get("snapshot_id") == sid,
                    wait_s, "follower catch-up",
                )

                # journal append tax: warm deltas riding the journal
                prev = np.asarray(
                    [res.resource_vector(n.get("usage", {}))
                     for n in nodes],
                    dtype=np.int64,
                )

                def warm_delta(bump):
                    nonlocal prev
                    cur = prev.copy()
                    cur[bump % cur.shape[0], 0] += 1 + bump
                    warm = pb2.SyncRequest()
                    warm.nodes.usage.CopyFrom(
                        numpy_to_tensor(cur, prev)
                    )
                    prev = cur
                    return warm.SerializeToString()

                for i in range(f_deltas):
                    sid = raw_sync(leader_sock, warm_delta(i)).snapshot_id
                wait_status(
                    fstatus, lambda s: s.get("snapshot_id") == sid,
                    wait_s, "follower delta catch-up",
                )
                # the leader's status loop ticks at 10 Hz; wait for it
                # to have SEEN every append before sampling the stats
                lstat = wait_status(
                    lstatus,
                    lambda s: (s.get("appends") or 0) >= f_deltas + 1,
                    wait_s, "leader journal append stats",
                )
                journal_append_us = lstat.get("last_append_us")
                phase(
                    "journal_appends",
                    appends=lstat.get("appends"),
                    last_append_us=journal_append_us,
                )

                # background read storm on the FOLLOWER: reads must
                # stay up while the leader dies, twice
                def storm():
                    while not storm_stop.is_set():
                        cur = read_status(fstatus).get("snapshot_id")
                        if not cur:
                            time.sleep(0.01)  # koordlint: disable=bare-retry(status-file poll pacing the load generator, not a retry)
                            continue
                        body = pb2.ScoreRequest(
                            snapshot_id=cur, top_k=8, flat=True
                        ).SerializeToString()
                        try:
                            status, out = raw_call(
                                fsock, METHOD_SCORE, body, timeout=30.0
                            )
                        except OSError:
                            status = 1
                        with reads_lock:
                            if status == 0:
                                reads["ok"] += 1
                                if in_failover.is_set():
                                    reads["ok_during_failover"] += 1
                            else:
                                reads["err"] += 1
                        time.sleep(0.005)  # koordlint: disable=bare-retry(fixed request pacing of the availability storm — errors are COUNTED, not retried)

                storm_threads = [
                    threading.Thread(target=storm, daemon=True)
                    for _ in range(4)
                ]
                for t in storm_threads:
                    t.start()
                resyncs_before = int(
                    read_status(fstatus).get("resyncs") or 0
                )

                # -- LEG A: SIGKILL -> journal warm-restart --
                in_failover.set()
                t_kill = time.perf_counter()
                leader.kill()
                leader.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
                leader = spawn_leader()
                procs.append(leader)
                lstat = wait_status(
                    lstatus,
                    lambda s: s.get("snapshot_id") == sid,
                    wait_s, "journal warm-restart onto the same chain",
                )
                journal_replay_ms = lstat.get("replay_ms")
                old_epoch, _old_gen = parse_snapshot_id(sid)
                reply = raw_sync(leader_sock, warm_delta(100))
                warm_restart_ms = (time.perf_counter() - t_kill) * 1000.0
                new_epoch, _new_gen = parse_snapshot_id(
                    reply.snapshot_id
                )
                assert new_epoch == old_epoch, (
                    "warm restart must resume the SAME epoch chain"
                )
                sid = reply.snapshot_id
                # the warm-restart split (ISSUE 20): how much of the
                # restart window was journal REPLAY vs jit COMPILE.
                # The status write that shows this boot's first append
                # lands after the sync-path compiles it attributes, so
                # waiting on appends>=1 makes compile_ms_total final.
                lstat = wait_status(
                    lstatus,
                    lambda s: (s.get("appends") or 0) >= 1,
                    wait_s, "restart compile attribution",
                )
                restart_replay_ms = journal_replay_ms
                restart_compile_ms = lstat.get("compile_ms_total")
                in_failover.clear()
                wait_status(
                    fstatus, lambda s: s.get("snapshot_id") == sid,
                    wait_s, "follower resume after warm restart",
                )
                resyncs_after_a = int(
                    read_status(fstatus).get("resyncs") or 0
                )
                phase(
                    "warm_restart",
                    warm_restart_ms=round(warm_restart_ms, 1),
                    journal_replay_ms=journal_replay_ms,
                    restart_replay_ms=restart_replay_ms,
                    restart_compile_ms=restart_compile_ms,
                    replayed_frames=lstat.get("replayed_frames"),
                    follower_resyncs=resyncs_after_a - resyncs_before,
                )

                # -- LEG B: SIGKILL -> follower promotion (SIGUSR2) --
                in_failover.set()
                t_kill = time.perf_counter()
                leader.kill()
                leader.wait()  # koordlint: disable=unbounded-wait(storm barrier; the parent _spawn window and _ArtifactDeadline bound the whole process)
                os.kill(follower.pid, _signal.SIGUSR2)
                fstat = wait_status(
                    fstatus, lambda s: s.get("promoted"), wait_s,
                    "follower promotion",
                )
                reply = raw_sync(fsock, warm_delta(200))
                failover_ms = (time.perf_counter() - t_kill) * 1000.0
                promoted_sid = reply.snapshot_id
                assert parse_snapshot_id(promoted_sid)[0] != old_epoch, (
                    "promotion must bump the epoch"
                )
                in_failover.clear()
                phase(
                    "promotion",
                    failover_ms=round(failover_ms, 1),
                    promoted_sid=promoted_sid,
                )
                storm_stop.set()
                for t in storm_threads:
                    t.join(timeout=30)
                resyncs_during_failover = int(
                    read_status(fstatus).get("resyncs") or 0
                ) - resyncs_before
                assert reads["ok_during_failover"] > 0, (
                    "reads must stay up while the leader is down"
                )
            finally:
                storm_stop.set()
                for p in procs:
                    try:
                        p.kill()
                    except OSError:
                        pass
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except sp.TimeoutExpired:
                        pass
        print(
            json.dumps(
                {
                    # the headline: leader-SIGKILL -> promoted follower
                    # ACKING WRITES again (the availability gap writes
                    # see; reads never stopped — asserted above)
                    "metric": "failover_promote_ms",
                    "value": round(failover_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "pods": f_pods,
                    "nodes": f_nodes,
                    "cpu_count": os.cpu_count() or 1,
                    "failover_ms": round(failover_ms, 2),
                    "warm_restart_ms": round(warm_restart_ms, 2),
                    # the restart window's split (ISSUE 20): journal
                    # replay vs jit compile — the compile share is what
                    # --config coldstart's cache+prewarm legs attack
                    "restart_replay_ms": restart_replay_ms,
                    "restart_compile_ms": restart_compile_ms,
                    "journal_replay_ms": journal_replay_ms,
                    "journal_append_us": journal_append_us,
                    "resyncs_during_failover": resyncs_during_failover,
                    "reads_during_failover": (
                        reads["ok_during_failover"]
                    ),
                    "spans": {
                        "warm_restart": round(warm_restart_ms, 2),
                        "promotion": round(failover_ms, 2),
                        "journal_replay": journal_replay_ms,
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "coldstart":
        _one_process_rule(backend, config)
        # ISSUE 20: kill the cold path.  Three legs, each judged
        # against its own unprewarmed oracle: (1) cold vs warm-cache
        # daemon boot — two REAL subprocess boots of the full
        # SchedulerServer sharing one persistent XLA cache dir + state
        # dir.  Boot 1 compiles everything cold and mints
        # <state>/prewarm.pkl; boot 2 reuses the disk cache and
        # AOT-replays the recorded signature set while it is already
        # serving.  The measured wall is spawn -> first-served flat
        # Score on the raw socket, and the score PAYLOAD digests must
        # match byte-for-byte (prewarm may only move compile time,
        # never bytes).  (2) boot 2's prewarm economics: signatures
        # replayed, compile wall, elapsed.  (3) the parallel cold
        # candidate build: the serial blocked sweep vs the pipelined
        # counts+extract build, both COLD against a fresh compile
        # cache in this process, byte-parity on (cand, count).
        import hashlib
        import socket as _socket
        import struct as _struct
        import subprocess as sp
        import tempfile

        from koordinator_tpu.bridge.codegen import pb2
        from koordinator_tpu.bridge.udsserver import (
            METHOD_SCORE,
            METHOD_SYNC,
        )
        from koordinator_tpu.harness.golden import build_sync_request

        c_pods = int(os.environ.get("KOORD_BENCH_COLDSTART_PODS", "256"))
        c_nodes = int(os.environ.get("KOORD_BENCH_COLDSTART_NODES", "64"))
        wait_s = float(
            os.environ.get("KOORD_BENCH_COLDSTART_WAIT", "240")
        )
        nodes, pods_l, gangs, quotas = generators.quota_colocation(
            pods=c_pods, nodes=c_nodes
        )
        req, _ = build_sync_request(nodes, pods_l, gangs, quotas)
        payload = req.SerializeToString()
        phase("scale", pods=c_pods, nodes=c_nodes)

        def raw_call(sock_path, method, body, timeout=60.0):
            conn = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
            conn.settimeout(timeout)
            try:
                conn.connect(sock_path)
                conn.sendall(
                    _struct.pack(">BI", method, len(body)) + body
                )
                hdr = b""
                while len(hdr) < 5:
                    chunk = conn.recv(5 - len(hdr))
                    if not chunk:
                        raise ConnectionError("closed mid-reply")
                    hdr += chunk
                status, ln = _struct.unpack(">BI", hdr)
                out = b""
                while len(out) < ln:
                    chunk = conn.recv(ln - len(out))
                    if not chunk:
                        raise ConnectionError("closed mid-reply")
                    out += chunk
                return status, out
            finally:
                conn.close()

        def read_status(path):
            try:
                with open(path) as fh:
                    return json.load(fh)
            except (OSError, ValueError):
                return {}

        def wait_status(path, pred, timeout_s, what):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if pred(read_status(path)):
                    return read_status(path)
                time.sleep(0.05)
            st = read_status(path)
            assert pred(st), f"timed out waiting for {what}: {st}"
            return st

        with tempfile.TemporaryDirectory() as tmp:
            cache_dir = os.path.join(tmp, "xla-cache")
            state_dir = os.path.join(tmp, "state")
            # the cold/warm boots share one FRESH cache, placed for
            # the children from outside; min compile time 0 so even
            # sub-second compiles land in it
            env = dict(
                os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            )

            def boot_and_score(tag):
                """Spawn one server subprocess against the SHARED cache
                + state dirs; returns (start_to_score_ms, payload
                digest, status path, process).  The wall starts before
                the spawn and stops on the first served flat Score —
                the daemon-readiness number an operator feels."""
                sock = os.path.join(tmp, f"{tag}.sock")
                status = os.path.join(tmp, f"{tag}.status.json")
                raw = sock + ".raw"
                t0 = time.perf_counter()
                proc = sp.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--coldstart-server",
                        "--platform", platform,
                        "--server-sock", sock,
                        "--server-state-dir", state_dir,
                        "--status-file", status,
                    ],
                    env=env, stdout=sp.DEVNULL,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
                deadline = t0 + wait_s
                while True:
                    try:
                        code, out = raw_call(
                            raw, METHOD_SYNC, payload, timeout=wait_s
                        )
                        assert code == 0, out[:200]
                        break
                    except (OSError, ConnectionError):
                        assert time.perf_counter() < deadline, (
                            f"{tag} boot never served its socket"
                        )
                        time.sleep(0.02)  # koordlint: disable=bare-retry(socket-bind poll: the daemon is still booting, connect errors ARE the signal)
                sid = pb2.SyncReply.FromString(out).snapshot_id
                body = pb2.ScoreRequest(
                    snapshot_id=sid, top_k=8, flat=True
                ).SerializeToString()
                code, out = raw_call(
                    raw, METHOD_SCORE, body, timeout=wait_s
                )
                assert code == 0, out[:200]
                start_ms = (time.perf_counter() - t0) * 1000.0
                flat = pb2.ScoreReply.FromString(out).flat
                digest = hashlib.sha256(
                    flat.pod_index + flat.counts + flat.node_index
                    + flat.score
                ).hexdigest()
                return start_ms, digest, status, proc

            # -- leg 1a: COLD boot (empty cache, no prewarm file) ----
            cold_ms, cold_digest, status1, proc1 = boot_and_score("cold")
            try:
                # the score path's capture flushed prewarm.pkl before
                # the reply was served; wait for the status loop to
                # confirm the runner idled (nothing to replay on boot
                # 1) so the file set under state_dir is final
                wait_status(
                    status1,
                    lambda s: (s.get("prewarm") or {}).get("state")
                    == "done",
                    wait_s, "cold boot prewarm idle",
                )
            finally:
                proc1.kill()
                proc1.wait(timeout=30)
            assert os.path.exists(
                os.path.join(state_dir, "prewarm.pkl")
            ), "cold boot never minted the prewarm signature set"
            phase("cold_boot", cold_start_ms=round(cold_ms, 1))

            # -- leg 1b+2: WARM boot (shared cache + prewarm replay) -
            warm_ms, warm_digest, status2, proc2 = boot_and_score("warm")
            try:
                pstat = wait_status(
                    status2,
                    lambda s: (s.get("prewarm") or {}).get("state")
                    == "done",
                    wait_s, "warm boot prewarm completion",
                )["prewarm"]
            finally:
                proc2.kill()
                proc2.wait(timeout=30)
            assert warm_digest == cold_digest, (
                "warm-cache boot served different score bytes than "
                "the cold (unprewarmed-oracle) boot"
            )
            assert pstat.get("total", 0) >= 1, (
                "warm boot found no signatures to replay"
            )
            prewarm_ms = pstat.get("elapsed_ms")
            cold_start_speedup = cold_ms / warm_ms if warm_ms > 0 else None
            phase(
                "warm_boot",
                warm_cache_start_ms=round(warm_ms, 1),
                cold_start_speedup=(
                    round(cold_start_speedup, 3)
                    if cold_start_speedup else None
                ),
                prewarm=pstat,
            )

        # -- leg 3: parallel cold candidate build ------------------
        import jax.numpy as jnp

        from koordinator_tpu.config import CycleConfig
        from koordinator_tpu.model.snapshot import (
            ClusterSnapshot,
            GangTable,
            NodeBatch,
            PodBatch,
            QuotaTable,
        )
        from koordinator_tpu.solver.candidates import (
            _build,
            _build_pipelined,
        )

        B_NODES = int(
            os.environ.get("KOORD_BENCH_COLDSTART_BUILD_NODES")
            or (1 << 16)
        )
        B_PODS = int(
            os.environ.get("KOORD_BENCH_COLDSTART_BUILD_PODS") or 256
        )
        B_WIDTH = int(
            os.environ.get("KOORD_BENCH_COLDSTART_WIDTH") or 64
        )
        cfg_sparse = CycleConfig(candidate_width=B_WIDTH)
        R = res.NUM_RESOURCES
        _CPU_I = res.RESOURCE_INDEX[res.CPU]
        _MEM_I = res.RESOURCE_INDEX[res.MEMORY]
        _PODS_I = res.RESOURCE_INDEX[res.PODS]
        rng = np.random.default_rng(20)
        nalloc = np.zeros((B_NODES, R), np.int64)
        nalloc[:, _CPU_I] = 32_000
        nalloc[:, _MEM_I] = 128 * 1024
        nalloc[:, _PODS_I] = 256
        nreq = np.zeros((B_NODES, R), np.int64)
        nreq[:, _CPU_I] = 31_800  # 200m free < the 500m ask
        open_rows = rng.choice(
            B_NODES, size=max(1, B_WIDTH // 2), replace=False
        )
        nreq[open_rows, _CPU_I] = 0
        preq = np.zeros((B_PODS, R), np.int64)
        preq[:, _CPU_I], preq[:, _MEM_I] = 500, 512
        preq[:, _PODS_I] = 1
        snap_build = ClusterSnapshot(
            nodes=NodeBatch(
                allocatable=jnp.asarray(nalloc),
                requested=jnp.asarray(nreq),
                usage=jnp.asarray((nalloc * 0.3).astype(np.int64)),
                metric_fresh=jnp.ones(B_NODES, bool),
                valid=jnp.ones(B_NODES, bool),
            ),
            pods=PodBatch(
                requests=jnp.asarray(preq),
                estimated=jnp.asarray(preq),
                priority_class=jnp.zeros(B_PODS, np.int32),
                qos=jnp.zeros(B_PODS, np.int32),
                priority=jnp.full(B_PODS, 5000, np.int32),
                gang_id=jnp.full(B_PODS, -1, np.int32),
                quota_id=jnp.full(B_PODS, -1, np.int32),
                valid=jnp.ones(B_PODS, bool),
            ),
            gangs=GangTable(
                min_member=jnp.zeros(1, np.int32),
                valid=jnp.zeros(1, bool),
            ),
            quotas=QuotaTable(
                runtime=jnp.zeros((1, R), np.int64),
                used=jnp.zeros((1, R), np.int64),
                limited=jnp.zeros((1, R), bool),
                valid=jnp.zeros(1, bool),
            ),
        )
        # cold means COLD: the persistent compile cache is off around
        # both builds — a populated cache from a previous run would
        # quietly turn them into disk-cache hits
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            phase("cold_build_encode", nodes=B_NODES, pods=B_PODS,
                  width=B_WIDTH)
            t0 = time.perf_counter()
            cand_s, count_s = _build(snap_build, cfg=cfg_sparse)
            jax.block_until_ready((cand_s, count_s))
            cold_build_serial_ms = _ms(t0)
            t0 = time.perf_counter()
            cand_p, count_p = _build_pipelined(snap_build, cfg_sparse)
            jax.block_until_ready((cand_p, count_p))
            cold_build_ms = _ms(t0)
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        assert (
            np.asarray(cand_s).tobytes() == np.asarray(cand_p).tobytes()
            and np.asarray(count_s).tobytes()
            == np.asarray(count_p).tobytes()
        ), "pipelined cold build diverged from the serial oracle"
        cold_build_speedup = (
            cold_build_serial_ms / cold_build_ms
            if cold_build_ms > 0 else None
        )
        phase(
            "cold_build",
            cold_build_serial_ms=round(cold_build_serial_ms, 1),
            cold_build_ms=round(cold_build_ms, 1),
            cold_build_speedup=(
                round(cold_build_speedup, 3)
                if cold_build_speedup else None
            ),
        )

        print(
            json.dumps(
                {
                    # the headline: spawn -> first-served Score with
                    # the persistent cache + prewarm file warm — the
                    # restart wall the cold path used to charge
                    "metric": "warm_cache_start_ms",
                    "value": round(warm_ms, 2),
                    "unit": "ms",
                    "backend": backend,
                    "pods": c_pods,
                    "nodes": c_nodes,
                    "cpu_count": os.cpu_count() or 1,
                    "cold_start_ms": round(cold_ms, 2),
                    "warm_cache_start_ms": round(warm_ms, 2),
                    "cold_start_speedup": (
                        round(cold_start_speedup, 3)
                        if cold_start_speedup else None
                    ),
                    "prewarm_ms": prewarm_ms,
                    "prewarm_signatures": pstat.get("total"),
                    "prewarm_compiled": pstat.get("compiled"),
                    "prewarm_compile_ms": pstat.get("compile_ms_total"),
                    "cold_build_serial_ms": round(
                        cold_build_serial_ms, 2
                    ),
                    "cold_build_ms": round(cold_build_ms, 2),
                    "cold_build_speedup": (
                        round(cold_build_speedup, 3)
                        if cold_build_speedup else None
                    ),
                    "build_nodes": B_NODES,
                    "spans": {
                        "cold_boot": round(cold_ms, 2),
                        "warm_boot": round(warm_ms, 2),
                        "cold_build_serial": round(
                            cold_build_serial_ms, 2
                        ),
                        "cold_build_pipelined": round(cold_build_ms, 2),
                    },
                }
            ),
            flush=True,
        )
        return

    if config == "rebalance":
        # BASELINE config #5: LowNodeLoad Balance tick over the same
        # 10k x 2k cluster, pods placed by the scheduling cycle
        from koordinator_tpu.constraints import build_quota_table_inputs
        from koordinator_tpu.descheduler.evictions import PodEvictor
        from koordinator_tpu.descheduler.lownodeload import (
            LowNodeLoadArgs,
            NodePool,
            balance,
        )
        from koordinator_tpu.solver import run_cycle

        snap, nodes, pods, gangs, quotas, qdicts = _quota_snapshot(
            encode_snapshot, generators, res, build_quota_table_inputs
        )
        result = run_cycle(snap)
        assignment = np.asarray(result.assignment)[: len(pods)]
        phase("cycle", path=result.path)

        node_dicts = [
            {
                "name": n["name"],
                "allocatable": n["allocatable"],
                "usage": n.get("usage", {}),
                "pods": [],
            }
            for n in nodes
        ]
        for p, a in enumerate(assignment):
            if a >= 0:
                node_dicts[a]["pods"].append(
                    {
                        "name": pods[p]["name"],
                        "namespace": "default",
                        "requests": pods[p]["requests"],
                        "priority": pods[p].get("priority", 0),
                    }
                )
        args = LowNodeLoadArgs(
            node_pools=[
                NodePool(
                    low_thresholds={"cpu": 20, "memory": 20},
                    high_thresholds={"cpu": 50, "memory": 50},
                )
            ],
            dry_run=True,
        )
        times = []
        plans = []
        for _ in range(3):
            evictor = PodEvictor(dry_run=True)
            t0 = time.perf_counter()
            plans = balance(args, node_dicts, evictor)
            times.append(_ms(t0))
        print(
            json.dumps(
                {
                    "metric": "rebalance_10kpod_2knode_ms",
                    "value": round(min(times), 2),
                    "unit": "ms",
                    "backend": backend,
                    "planned_evictions": len(plans),
                }
            ),
            flush=True,
        )
        return

    raise SystemExit(f"unknown config {config!r}")


def _spawn(flag, platform, env_extra, timeout, config=None):
    """Run a child stage; returns (ok, final_json_line, err_string)."""
    env = dict(os.environ, **env_extra)
    argv = [
        sys.executable,
        os.path.abspath(__file__),
        flag,
        "--platform",
        platform,
    ]
    if config:
        argv += ["--config", config]
    try:
        proc = subprocess.run(
            argv,
            env=env,
            timeout=timeout,
            capture_output=True,
            text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as e:
        out = e.stdout or b""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        # a child that already printed its metric line but hung in a later
        # best-effort stage (e.g. the native baseline) still produced a
        # valid artifact — never discard a finished measurement.  A
        # truncated line is NOT one (children don't arm the deadline, but
        # a group-wide SIGTERM from the driver can still reach them): let
        # the fallback chain keep trying instead of publishing value -1.
        finals = [
            l
            for l in out.splitlines()
            if l.startswith('{"metric"') and '"truncated": true' not in l
        ]
        if finals:
            return True, finals[-1], ""
        phases = [l for l in out.splitlines() if l.startswith('{"phase"')]
        return (
            False,
            None,
            f"{flag} timed out after {timeout}s; last phase: "
            f"{phases[-1] if phases else 'none (backend init hang)'}",
        )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    final = [l for l in lines if '"metric"' in l or '"probe"' in l]
    if proc.returncode == 0 and final:
        return True, final[-1], ""
    tail = proc.stderr.strip().splitlines()
    return (
        False,
        None,
        f"{flag} rc={proc.returncode}: {tail[-1] if tail else 'no stderr'}",
    )


def failover_leader(platform: str, sock: str, repl: str,
                    state_dir: str, status_file: str) -> None:
    """Leader worker for ``--config failover`` (ISSUE 11): one WRITER
    daemon in its own process — ScorerServicer on a raw-UDS socket,
    durable frame journal under ``state_dir`` replayed on boot (the
    warm-restart leg re-spawns this very worker against the same
    state dir), replication publisher serving journal-backed resume.
    Publishes boot/replay/journal stats to ``status_file`` so the
    bench can assert the same-chain resume and read the append tax
    without an RPC.  Exits when its parent disappears — a
    deadline-killed bench leaks nothing."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from koordinator_tpu.bridge.server import ScorerServicer
    from koordinator_tpu.bridge.udsserver import RawUdsServer
    from koordinator_tpu.obs import devprof
    from koordinator_tpu.replication.journal import FrameJournal
    from koordinator_tpu.replication.leader import ReplicationPublisher

    # compile attribution for the warm-restart split (ISSUE 20): the
    # ledger's compile capture fires on every boundary's FIRST launch
    # and adds no per-launch sync, so warm_restart_ms stays clean
    devprof.configure(attribute=True)
    sv = ScorerServicer(score_memo=False, score_incr=False)
    os.makedirs(state_dir, exist_ok=True)
    journal = FrameJournal(os.path.join(state_dir, "journal.krj"))
    replay = journal.recover(sv)
    journal.attach(sv)
    server = RawUdsServer(sock, servicer=sv).start()
    pub = ReplicationPublisher(sv, repl, journal=journal).attach().start()

    def write_status():
        try:
            st = journal.stats()
            tmp_path = status_file + ".tmp"
            with open(tmp_path, "w") as fh:
                json.dump(
                    {
                        "snapshot_id": sv.snapshot_id(),
                        "replay_ms": replay["replay_ms"],
                        "replayed_frames": replay["replayed_frames"],
                        "truncated": replay["truncated"],
                        "appends": st["appends"],
                        "last_append_us": st["last_append_us"],
                        "journal_bytes": st["bytes"],
                        # cumulative jit-compile wall this process paid
                        # (devprof ledger): a freshly respawned leader's
                        # value IS the restart's compile share
                        "compile_ms_total": (
                            devprof.health_block()["compile_ms_total"]
                        ),
                    },
                    fh,
                )
            os.replace(tmp_path, status_file)
        except OSError:
            pass  # status is observability; the leader keeps serving

    ppid = os.getppid()
    try:
        while os.getppid() == ppid:
            write_status()
            time.sleep(0.1)
    except KeyboardInterrupt:
        pass
    finally:
        pub.stop()
        server.stop()
        journal.close()


def coldstart_server(platform: str, sock: str, state_dir: str,
                     status_file: str) -> None:
    """Server worker for ``--config coldstart`` (ISSUE 20): one full
    ``SchedulerServer`` in its own process with both cold-path killers
    ON — the persistent compile cache pointed at the bench's shared
    directory (JAX_COMPILATION_CACHE_DIR from the parent; min-compile
    threshold 0 so even the CPU leg's sub-second compiles land in it)
    and ``--prewarm`` (signature capture into <state>/prewarm.pkl plus
    boot-time AOT replay of the previous incarnation's set).
    Publishes snapshot id + prewarm progress to ``status_file``; exits
    when its parent disappears so a deadline-killed bench leaks
    nothing."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from koordinator_tpu.scheduler.server import SchedulerServer

    os.makedirs(state_dir, exist_ok=True)
    srv = SchedulerServer(
        lease_path=os.path.join(state_dir, "leader.lease"),
        uds_path=sock,
        http_port=0,
        enable_grpc=False,
        state_dir=state_dir,
        prewarm=True,
    ).start()

    def write_status():
        try:
            tmp_path = status_file + ".tmp"
            with open(tmp_path, "w") as fh:
                json.dump(
                    {
                        "snapshot_id": srv.servicer.snapshot_id(),
                        "prewarm": srv.prewarm_health(),
                    },
                    fh,
                )
            os.replace(tmp_path, status_file)
        except OSError:
            pass  # status is observability; the server keeps serving

    ppid = os.getppid()
    try:
        while os.getppid() == ppid:
            write_status()
            time.sleep(0.05)
    except KeyboardInterrupt:
        pass
    finally:
        srv.stop()


def replica_follower(platform: str, sock: str, replicate_from: str,
                     status_file: str, promote_repl=None,
                     promote_state_dir=None) -> None:
    """Follower worker for ``--config replica`` (ISSUE 8): one READ
    REPLICA daemon in its own process — FollowerServicer on a raw-UDS
    socket, subscribed to the leader's replication socket, publishing
    its chain position to ``status_file`` after every applied frame so
    the bench can wait for catch-up and read the lag without an RPC.
    Exits when its parent (the bench child) disappears, so a
    deadline-killed bench never leaks follower processes.

    ``--config failover`` (ISSUE 11) reuses this worker with
    ``promote_repl``/``promote_state_dir`` set: on SIGUSR2 the replica
    PROMOTES — subscription stopped, epoch bumped, its own journal
    seeded and publisher started on ``promote_repl`` — and the status
    file flips ``promoted`` with the new chain id."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from koordinator_tpu.bridge.udsserver import RawUdsServer
    from koordinator_tpu.replication.follower import (
        FollowerServicer,
        ReplicaApplier,
        ReplicationSubscriber,
    )

    kw = {}
    if os.environ.get("KOORD_COALESCE_CAP_MS"):
        kw["coalesce_cap_ms"] = float(os.environ["KOORD_COALESCE_CAP_MS"])
    # the follower serves the replica storm's reads: same storm rule —
    # memo and incremental engine off so every Score pays real rescoring
    sv = FollowerServicer(score_memo=False, score_incr=False,
                          leader=replicate_from, **kw)
    applier = ReplicaApplier(sv)
    promoted = {"flag": False, "sid": None}

    def write_status():
        try:
            tmp_path = status_file + ".tmp"
            with open(tmp_path, "w") as fh:
                json.dump(
                    {
                        "snapshot_id": sv.snapshot_id(),
                        "lag_ms": applier.last_lag_ms,
                        "applied": applier.applied,
                        "resyncs": applier.resyncs,
                        "promoted": promoted["flag"],
                    },
                    fh,
                )
            os.replace(tmp_path, status_file)
        except OSError:
            pass  # status is observability; the replica keeps serving

    def on_frame(result, frame):
        write_status()

    server = RawUdsServer(sock, servicer=sv).start()
    sub = ReplicationSubscriber(
        replicate_from, applier, on_frame=on_frame
    ).start()
    pub = None
    journal = None
    promote_evt = threading.Event()
    if promote_repl:
        import signal as _signal

        _signal.signal(
            _signal.SIGUSR2, lambda signum, frame: promote_evt.set()
        )
    ppid = os.getppid()
    try:
        while os.getppid() == ppid:
            if promote_evt.is_set() and not promoted["flag"]:
                # the failover-config promote path: subscription down,
                # epoch bumped, own journal + publisher up
                sub.stop()
                promoted["sid"] = sv.promote()
                if promote_state_dir:
                    from koordinator_tpu.replication.journal import (
                        FrameJournal,
                    )

                    os.makedirs(promote_state_dir, exist_ok=True)
                    journal = FrameJournal(
                        os.path.join(promote_state_dir, "journal.krj")
                    )
                    epoch, gen, payload = (
                        sv.export_replication_snapshot()
                    )
                    journal.write_base(epoch, gen, payload)
                    journal.attach(sv)
                from koordinator_tpu.replication.leader import (
                    ReplicationPublisher,
                )

                pub = ReplicationPublisher(
                    sv, promote_repl, journal=journal
                ).attach().start()
                promoted["flag"] = True
            write_status()
            time.sleep(0.1)
    except KeyboardInterrupt:
        pass
    finally:
        sub.stop()
        if pub is not None:
            pub.stop()
        if journal is not None:
            journal.close()
        server.stop()


def replica_storm(sock: str, snapshot_id: str, clients: int,
                  reps: int) -> None:
    """Client-storm worker for ``--config replica`` (ISSUE 8): N
    worker threads against ONE daemon socket, with the warm-up/GO
    handshake on stdio so M such processes release their storms
    together.  A separate process per replica's clients because a
    single bench-process GIL would pace all M x N arrivals and starve
    the very coalescers the storm measures."""
    def on_start():
        # _score_storm calls this after every warm-up completed and
        # strictly before any timed request can fire
        print("STORM_READY", flush=True)
        sys.stdin.readline()  # GO

    wall, _lats, digests, errors = _score_storm(
        sock, snapshot_id, clients, reps, on_start=on_start
    )
    print(
        json.dumps(
            {
                "storm_wall_s": wall,
                "digests": sorted(digests),
                "errors": [str(e) for e in errors],
            }
        ),
        flush=True,
    )


def probe(platform: str) -> None:
    """Minimal backend touch: init + one tiny op."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    x = jax.numpy.zeros(8).sum()
    x.block_until_ready()
    print(
        json.dumps(
            {"probe": jax.default_backend(), "devices": len(jax.devices())}
        ),
        flush=True,
    )


def _env_seconds(name: str, default: float) -> float:
    """Env override parsed defensively: a malformed value must degrade to
    the default, never crash parent() before its one JSON line."""
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


class _Budget:
    """Total-wall-clock accountant: stage windows are derived from what
    remains, and a CPU-fallback slot is always held back so the last
    stage can still print an artifact line inside the driver's timeout.

    Invariant (tests/test_bench_budget.py drives it with a fake clock):
    the sum of every granted window plus the inter-probe sleeps never
    exceeds ``total`` — a run once ended rc=124 with NO artifact because
    stage windows could overshoot the budget the driver enforces.

    ``clock`` is injectable so the stdlib-only budget test can replay
    the parent's exact request sequence without waiting wall-clock.
    """

    def __init__(self, total: float, reserve: float, clock=time.monotonic):
        self._clock = clock
        self.start = clock()
        self.total = total
        self.reserve = reserve

    def remaining(self) -> float:
        return max(0.0, self.total - (self._clock() - self.start))

    def window(self, want: float, reserve: Optional[float] = None) -> float:
        """Clamp a desired stage window to the budget, holding back the
        CPU-fallback reserve (pass reserve=0 for the fallback itself)."""
        keep = self.reserve if reserve is None else reserve
        return max(0.0, min(want, self.remaining() - keep))


def _probe_until(budget: "_Budget", window_seconds: float):
    """Probe for a LIVE TPU repeatedly until the window closes.

    A TPU backend can be down for minutes and come back, and a dead
    backend shows up in TWO ways: the probe child hangs/errors, or jax silently demotes to
    the CPU backend and the probe "succeeds" reporting cpu.  Both are
    retryable non-answers here — the bench fights for a TPU artifact
    across the whole window.  Returns (tpu_alive, errors)."""
    deadline = time.monotonic() + budget.window(window_seconds)
    errors = []
    while True:
        left = deadline - time.monotonic()
        if left <= 0 or budget.window(PROBE_TIMEOUT) <= 0:
            return False, errors[-2:]
        ok, out, err = _spawn(
            "--probe", "default", {}, max(1.0, min(PROBE_TIMEOUT, left))
        )
        if ok and '"probe": "cpu"' not in (out or ""):
            return True, errors[-2:]
        errors.append(err if not ok else "probe demoted to cpu backend")
        if time.monotonic() >= deadline:
            return False, errors[-2:]
        # clamp to the window: an unclamped 30s sleep at deadline-epsilon
        # overshoots the budget the CPU-fallback reserve depends on (and
        # the monotonic() here races the deadline check above, so the
        # remainder can already be negative — sleep(-x) raises)
        time.sleep(max(0.0, min(30.0, deadline - time.monotonic())))


def _stamp_tpu_probe(final, outcome):
    """Stamp the parent's TPU-probe outcome onto the child's artifact
    line (ISSUE 19): WHY a run landed on the
    leg it did must ride the artifact itself — a probe verdict logged
    to stderr is discarded with the rest of the run's logs, and a CPU
    artifact with no outage context reads as a kernel regression.  A
    line that does not parse is returned untouched (the schema
    validator rejects it downstream anyway)."""
    try:
        doc = json.loads(final)
    except (TypeError, ValueError):
        return final
    doc["tpu_probe"] = outcome
    return json.dumps(doc)


def parent() -> int:
    """Probe, then measure with retries + hard timeouts; ONE JSON line,
    inside KOORD_BENCH_TOTAL_BUDGET seconds under every failure mode."""
    # The CPU fallback's slot is reserved from the start; the TPU probe
    # window (default 4 min — a longer wait spent the driver's whole
    # deadline probing and published NOTHING)
    # shrinks to whatever the total budget leaves after that
    # reservation — artifact first, probing second.
    budget = _Budget(
        _env_seconds("KOORD_BENCH_TOTAL_BUDGET", TOTAL_BUDGET),
        # the extra 60s absorbs probe-loop and process-spawn drift so the
        # CPU child's window is still intact when the fallback runs
        reserve=CPU_TIMEOUT + 60.0,
    )
    _PROGRESS["stage"] = "tpu_probe"
    tpu_alive, errors = _probe_until(
        budget, _env_seconds("KOORD_BENCH_TPU_WAIT", 240.0)
    )
    _PROGRESS["errors"] = errors
    if tpu_alive:
        # fight for the TPU across the remaining window: up to three
        # attempts with a fresh backend probe between retries, so a
        # transient backend hiccup mid-run doesn't demote the artifact
        for attempt, timeout in enumerate(
            (TPU_TIMEOUT, TPU_TIMEOUT, TPU_TIMEOUT * 3 // 4)
        ):
            timeout = budget.window(timeout)
            if timeout <= 60:
                errors.append("tpu attempt skipped: budget exhausted")
                break
            _PROGRESS["stage"] = f"tpu_attempt_{attempt + 1}"
            ok, final, err = _spawn("--child", "default", {}, timeout)
            if ok:
                if _emit_artifact(_stamp_tpu_probe(final, "live")):
                    return 0
                err = "tpu artifact failed schema validation"
            errors.append(err)
            if attempt < 2:
                if budget.window(PROBE_TIMEOUT) <= 0:
                    errors.append("reprobe skipped: budget exhausted")
                    break
                ok, pout, perr = _spawn(
                    "--probe", "default", {}, budget.window(PROBE_TIMEOUT)
                )
                # same demotion check as the initial gate: a dead backend
                # makes jax fall back to CPU, so a "successful" probe that
                # reports cpu must leave the TPU branch, not retry it
                if not ok or '"probe": "cpu"' in (pout or ""):
                    errors.append(f"reprobe: {perr or 'backend demoted to cpu'}")
                    break
    # TPU never came up (or exhausted its retry budget): virtual-CPU
    # fallback so an artifact exists either way; "backend" in the line
    # records the truth, and "note" records WHY it is cpu so a reader
    # does not misread a platform outage as a performance regression.
    # The window is whatever honestly remains — NOT floored at 60s: a
    # floor above the remaining budget is exactly the overshoot that
    # let the driver's axe land before the artifact printed (r05), and
    # the reserve guarantees a full CPU slot in every normal run.
    cpu_window = budget.window(CPU_TIMEOUT, reserve=0.0)
    if cpu_window > 0:
        _PROGRESS["stage"] = "cpu_fallback"
        ok, final, err = _spawn("--child", "cpu", _CPU_ENV, cpu_window)
    else:
        ok, final, err = False, None, "cpu fallback skipped: budget exhausted"
    if ok:
        try:
            doc = json.loads(final)
            doc["note"] = (
                "tpu bench attempts failed after a live probe "
                "(mid-run outage or demotion); "
                if tpu_alive
                else "tpu backend unreachable for the whole probe window; "
            ) + "cpu fallback measures the scan path, not the kernel"
            doc["tpu_probe"] = (
                "live-then-lost" if tpu_alive else "unreachable"
            )
            final = json.dumps(doc)
        except ValueError:
            pass
        if _emit_artifact(final):
            return 0
        errors.append("cpu artifact failed schema validation")
    else:
        errors.append(err)
    _emit_artifact(
        json.dumps(
            {
                "metric": METRIC,
                "value": -1,
                "unit": "ms",
                "vs_baseline": 0.0,
                "error": "; ".join(errors),
                "tpu_probe": (
                    "live-then-lost" if tpu_alive else "unreachable"
                ),
            }
        )
    )
    return 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--platform", default="default", choices=["default", "cpu"])
    ap.add_argument(
        "--config",
        default=None,
        choices=[
            "spark", "loadaware", "gang", "extras", "rebalance", "smoke",
            "bridge", "mesh", "replica", "failover", "trace",
            "chaos-trace", "plugins", "sparse", "tree", "coldstart",
        ],
        help="measure a secondary BASELINE config instead of the headline "
        "10k x 2k quota_colocation cycle (driver contract: no args prints "
        "exactly the one headline JSON line)",
    )
    ap.add_argument(
        "--replica-follower", action="store_true",
        help="internal: run one read-replica daemon for --config "
        "replica (spawned by the bench child, never by the driver)",
    )
    ap.add_argument("--follower-sock", default=None)
    ap.add_argument("--replicate-from", default=None)
    ap.add_argument("--status-file", default=None)
    ap.add_argument("--promote-repl", default=None)
    ap.add_argument("--promote-state-dir", default=None)
    ap.add_argument(
        "--failover-leader", action="store_true",
        help="internal: run the journaled leader daemon for --config "
        "failover (spawned by the bench child, never by the driver)",
    )
    ap.add_argument("--leader-sock", default=None)
    ap.add_argument("--leader-repl", default=None)
    ap.add_argument("--leader-state-dir", default=None)
    ap.add_argument(
        "--coldstart-server", action="store_true",
        help="internal: run one prewarm-enabled scheduler daemon for "
        "--config coldstart (spawned by the bench child, never by "
        "the driver)",
    )
    ap.add_argument("--server-sock", default=None)
    ap.add_argument("--server-state-dir", default=None)
    ap.add_argument(
        "--replica-storm", action="store_true",
        help="internal: one replica's client storm for --config "
        "replica (spawned by the bench child, never by the driver)",
    )
    ap.add_argument("--storm-sock", default=None)
    ap.add_argument("--storm-clients", type=int, default=16)
    ap.add_argument("--storm-reps", type=int, default=3)
    ap.add_argument("--storm-snapshot", default=None)
    args = ap.parse_args()
    if args.failover_leader:
        failover_leader(
            args.platform, args.leader_sock, args.leader_repl,
            args.leader_state_dir, args.status_file,
        )
        return 0
    if args.coldstart_server:
        coldstart_server(
            args.platform, args.server_sock, args.server_state_dir,
            args.status_file,
        )
        return 0
    if args.replica_follower:
        replica_follower(
            args.platform, args.follower_sock, args.replicate_from,
            args.status_file,
            promote_repl=args.promote_repl,
            promote_state_dir=args.promote_state_dir,
        )
        return 0
    if args.replica_storm:
        replica_storm(
            args.storm_sock, args.storm_snapshot, args.storm_clients,
            args.storm_reps,
        )
        return 0
    if args.probe:
        probe(args.platform)
        return 0
    if args.config and args.child:
        child_config(args.platform, args.config)
        return 0
    if args.child:
        child(args.platform)
        return 0
    # ONLY parent paths beyond this point — they own the one-artifact
    # contract: arm the hard deadline + SIGTERM flush so rc=124 can
    # never again mean "no artifact".  Children must NOT arm it: they
    # are bounded by the parent's _spawn windows, and a truncated child
    # line on the stdout pipe would read as a finished measurement in
    # _spawn's timeout salvage.
    global _DEADLINE
    _DEADLINE = _ArtifactDeadline(
        _env_seconds("KOORD_BENCH_TOTAL_BUDGET", TOTAL_BUDGET),
        metric=args.config or METRIC,
    ).install()
    if args.config:
        # same probe + budget machinery as the headline parent (shorter
        # default probe window: configs are secondary artifacts)
        budget = _Budget(
            _env_seconds("KOORD_BENCH_TOTAL_BUDGET", TOTAL_BUDGET),
            reserve=CPU_TIMEOUT + 60.0,
        )
        _PROGRESS["stage"] = f"config_{args.config}_probe"
        tpu_alive, errors = _probe_until(
            budget, _env_seconds("KOORD_BENCH_TPU_WAIT_CONFIG", 240.0)
        )
        if tpu_alive:
            window = budget.window(TPU_TIMEOUT)
            if window > 60:
                _PROGRESS["stage"] = f"config_{args.config}_tpu"
                ok, out, err = _spawn(
                    "--child", "default", {}, window, config=args.config
                )
                if ok:
                    if _emit_artifact(_stamp_tpu_probe(out, "live")):
                        return 0
                    err = "tpu config artifact failed schema validation"
                errors.append(err)
            else:
                errors.append("tpu attempt skipped: budget exhausted")
        cpu_window = budget.window(CPU_TIMEOUT, reserve=0.0)
        if cpu_window > 0:
            _PROGRESS["stage"] = f"config_{args.config}_cpu"
            ok, out, err = _spawn(
                "--child", "cpu",
                _MESH_CPU_ENV if args.config == "mesh" else _CPU_ENV,
                cpu_window, config=args.config,
            )
        else:
            ok, out, err = (
                False, None, "cpu fallback skipped: budget exhausted"
            )
        if ok:
            out = _stamp_tpu_probe(
                out, "live-then-lost" if tpu_alive else "unreachable"
            )
            if _emit_artifact(out):
                return 0
            errors.append("cpu config artifact failed schema validation")
        else:
            errors.append(err)
        _emit_artifact(
            json.dumps(
                {"metric": args.config, "value": -1, "error": "; ".join(errors),
                 "tpu_probe": (
                     "live-then-lost" if tpu_alive else "unreachable")}
            )
        )
        return 1
    return parent()


if __name__ == "__main__":
    sys.exit(main())
