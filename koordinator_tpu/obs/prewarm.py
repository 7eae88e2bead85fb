"""AOT signature prewarm: kill the cold path's compile ladder (ISSUE 20).

The devprof launch ledger (obs/devprof.py) already labels every
serving-path jit boundary and the static signatures it minted; with
``--prewarm`` the ledger additionally captures, per signature, an
abstract replay spec (array leaves as ``jax.ShapeDtypeStruct``, statics
pickled as-is) persisted as ``<state-dir>/prewarm.pkl``.  On the next
boot — and again on follower promotion and on autoscaler spawn — a
:class:`PrewarmRunner` background thread replays that set through
``fn.lower(*spec).compile()`` in ledger-hot order (most-launched
signatures first) *while the server is already accepting RPCs*:

* a request whose signature the runner has not reached yet just
  compiles inline, exactly as today — prewarm is an accelerant, never
  a gate, and the breaker/brownout ladder is untouched;
* each replayed compile lands in the persistent XLA cache (the
  package's one rule: ``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``), so even the inline-compile fallback pays
  trace time only, not backend compile time;
* replayed signatures land in the compile ledger via
  ``devprof.record_prewarm_compile`` — warm, but NOT attributed
  retraces (replaying yesterday's shapes is the expected boot path).

Progress is observable three ways: the ``koord_scorer_prewarm_*``
metric families, the /healthz ``prewarm`` block
(:meth:`PrewarmRunner.stats`), and the coldstart bench artifact's
``prewarm_ms``.

The two module tables below are the lint-checked contract
(``koordlint prewarm-drift``, analysis/prewarmdrift.py): every
``@devprof.boundary``-registered name must appear in exactly one of
them, so the replay set can never silently rot as boundaries are
added.  ``PREWARM_EXCLUDED`` names boundaries whose signatures carry a
process-local static (a ``jax.sharding.Mesh``) that cannot ride a
pickle — their capture marks them non-replayable and the runner skips
them; everything else is replayable and listed in
``PREWARM_BOUNDARIES``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from koordinator_tpu.obs import devprof

__all__ = [
    "PREWARM_BOUNDARIES",
    "PREWARM_EXCLUDED",
    "PrewarmRunner",
]

# Replayable jit boundaries: statics pickle (frozen CycleConfig, ints,
# bools), so a prior incarnation's signatures replay through the AOT
# seam.  Keep sorted; koordlint prewarm-drift diffs this table against
# every @devprof.boundary registration in the repo, both directions.
PREWARM_BOUNDARIES = (
    "solver.candidates._build",
    "solver.candidates._count_blocks",
    "solver.candidates._extract_block",
    "solver.candidates._refresh",
    "solver.candidates._score",
    "solver.candidates.sparse_top_k",
    "solver.greedy.greedy_assign",
    "solver.greedy.score_cycle",
    "solver.incremental._rescore",
    "solver.pallas_dense._greedy_assign_dense",
    "solver.pallas_dense._run_cycle_dense",
    "solver.resident._scatter_flat",
    "solver.terms._term_extras_jit",
    "solver.topk.masked_top_k",
)

# Boundaries prewarm can never replay, with the reason on record: their
# jit signature includes a process-local static no pickle can carry.
# Capture marks their specs non-replayable (spec=None) at record time;
# the runner counts them skipped.  A fresh mesh process re-compiles
# them inline once — and still hits the persistent XLA cache when the
# mesh geometry matches a prior incarnation's.
PREWARM_EXCLUDED: Dict[str, str] = {
    "parallel.shard_assign._assign_sharded": "mesh static is process-local",
    "parallel.shard_assign._assign_waves": "mesh static is process-local",
    "solver.candidates._build_sharded": "mesh static is process-local",
    "solver.candidates._count_blocks_sharded": "mesh static is process-local",
    "solver.candidates._refresh_sharded": "mesh static is process-local",
    "solver.candidates._score_sharded": "mesh static is process-local",
    "solver.incremental._rescore_sharded": "mesh static is process-local",
    "solver.resident._scatter_flat_sharded": "mesh static is process-local",
}

# modules whose import registers the serving boundaries; the runner
# imports them up front so name->fn resolution does not depend on the
# server having touched every engine before prewarm starts
_BOUNDARY_MODULES = (
    "koordinator_tpu.solver.candidates",
    "koordinator_tpu.solver.greedy",
    "koordinator_tpu.solver.incremental",
    "koordinator_tpu.solver.pallas_dense",
    "koordinator_tpu.solver.resident",
    "koordinator_tpu.solver.terms",
    "koordinator_tpu.solver.topk",
    "koordinator_tpu.parallel.shard_assign",
)


def _import_boundary_modules() -> None:
    import importlib

    for mod in _BOUNDARY_MODULES:
        try:
            importlib.import_module(mod)
        except Exception:  # koordlint: disable=broad-except(reason: a backend-gated engine module (pallas on a cpu-only build) failing to import just leaves its boundaries unresolvable — those records are counted skipped, the rest prewarm)
            pass


class PrewarmRunner:
    """Replay a persisted signature set on a background thread.

    One-shot: :meth:`start` spawns the daemon thread, :meth:`stats`
    is the /healthz ``prewarm`` block, :meth:`wait` is the test/bench
    barrier.  Re-triggering (promotion, a fresh autoscaler replica)
    constructs a NEW runner — replays of already-compiled signatures
    cost one trace each and hit both caches.
    """

    def __init__(self, state_dir: str, metrics=None):
        self.state_dir = str(state_dir)
        self._metrics = metrics
        self._lock = threading.Lock()
        self._state = "idle"  # idle -> running -> done
        self._total = 0
        self._replayable = 0
        self._compiled = 0
        self._skipped = 0
        self._failed = 0
        self._compile_ms_total = 0.0
        self._elapsed_ms: Optional[float] = None
        self._stop = threading.Event()
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------
    def start(self) -> "PrewarmRunner":
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="koord-prewarm"
        )
        with self._lock:
            self._state = "running"
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout=timeout if timeout is not None
                               else 1.0)

    # -- the replay loop ---------------------------------------------
    def _run(self) -> None:
        import pickle

        t0 = time.perf_counter()
        records = devprof.load_prewarm(self.state_dir)
        _import_boundary_modules()
        # future dumps from THIS process must keep yesterday's
        # signatures even if today's traffic never replays them all
        devprof.load_replays(records)
        with self._lock:
            self._total = len(records)
            self._replayable = sum(1 for r in records if r.get("spec"))
        self._gauge(self._replayable)
        pending = self._replayable
        for rec in records:
            if self._stop.is_set():
                break
            spec = rec.get("spec")
            if not spec:
                self._count("skipped")
                continue
            fn = devprof.boundary_fn(rec["boundary"])
            if fn is None or not hasattr(fn, "lower"):
                self._count("skipped")
                pending -= 1
                self._gauge(pending)
                continue
            try:
                args, kwargs = pickle.loads(spec)
                c0 = time.perf_counter()
                compiled = fn.lower(*args, **kwargs).compile()
                compile_ms = (time.perf_counter() - c0) * 1e3
            except Exception:  # koordlint: disable=broad-except(reason: a stale spec (code drift since capture, backend drift) must cost one replay slot, never the serving process — the live path compiles inline as before)
                self._count("failed")
                pending -= 1
                self._gauge(pending)
                continue
            devprof.record_prewarm_compile(
                rec["boundary"], rec["sig"],
                _backend() or "unknown", compile_ms,
                devprof._cost_dict(compiled), devprof._mem_dict(compiled),
            )
            with self._lock:
                self._compiled += 1
                self._compile_ms_total += compile_ms
            m = self._metrics
            if m is not None:
                try:
                    m.count_prewarm("compiled")
                    m.add_prewarm_compile_ms(compile_ms)
                except Exception:  # koordlint: disable=broad-except(reason: telemetry sink drift must not break the prewarm loop; the runner's own counters already recorded the replay)
                    pass
            pending -= 1
            self._gauge(pending)
        with self._lock:
            self._state = "done"
            self._elapsed_ms = (time.perf_counter() - t0) * 1e3
        self._gauge(0)
        self._done.set()

    def _count(self, result: str) -> None:
        with self._lock:
            if result == "skipped":
                self._skipped += 1
            elif result == "failed":
                self._failed += 1
        m = self._metrics
        if m is not None:
            try:
                m.count_prewarm(result)
            except Exception:  # koordlint: disable=broad-except(reason: telemetry sink drift must not break the prewarm loop; the runner's own counters already recorded the outcome)
                pass

    def _gauge(self, pending: int) -> None:
        m = self._metrics
        if m is not None:
            try:
                m.set_prewarm_pending(max(0, int(pending)))
            except Exception:  # koordlint: disable=broad-except(reason: telemetry sink drift must not break the prewarm loop)
                pass

    # -- views -------------------------------------------------------
    def stats(self) -> dict:
        """The /healthz ``prewarm`` block."""
        with self._lock:
            return {
                "state": self._state,
                "total": self._total,
                "replayable": self._replayable,
                "compiled": self._compiled,
                "skipped": self._skipped,
                "failed": self._failed,
                "compile_ms_total": round(self._compile_ms_total, 3),
                "elapsed_ms": (
                    round(self._elapsed_ms, 3)
                    if self._elapsed_ms is not None else None
                ),
            }


def _backend() -> Optional[str]:
    return devprof._backend_platform()
