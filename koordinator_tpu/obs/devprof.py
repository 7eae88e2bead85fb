"""Compile truth: the XLA launch ledger (ISSUE 19).

Every jit boundary in the serving path registers here with
:func:`boundary`.  With attribution on (``configure(attribute=True)``)
the ledger captures, per (boundary, static shape signature), at
first-compile time via the AOT path (``fn.lower(*args).compile()``):
compile wall-time, XLA ``cost_analysis()`` (flops, bytes accessed) and
``memory_analysis()`` (temp/argument/output bytes), labeled by backend
platform.  A retrace is therefore not just a counter bump
(``koord_scorer_jit_cache_miss_total``) but an **attributed event**
naming the boundary and the shape signature that minted it.

Device time is not sampled here: a profiler trace (the benchmark's
``--trace 1`` run, or the daemon's admin ``profile`` capture,
:func:`capture_profile`) shows every device operation beside the
``koord.*`` host stages that obs/spans.py annotates.

The ledger feeds the ``koord_scorer_devprof_*`` compile families on
/metrics, the /healthz ``device`` block, the prewarm replay set
(capture mode, ISSUE 20) and the report CLI::

    python -m koordinator_tpu.obs.devprof <state-dir>

The hard contract, inherited from the warm path's compile economics
(docs/ANALYSIS.md "instrumentation never enters jitted code"):

* attribution and capture off (the default) is **bit-inert**: the
  wrapper short-circuits to ``fn(*args, **kwargs)`` before touching
  anything — no signature hashing, no AOT, zero retraces.
* A boundary invoked while a jax trace is live (nested jits: the
  Pallas cycle calling ``score_cycle``, term extras fused inside
  ``score_all``) bypasses ALL instrumentation — only outermost,
  host-invoked launches are attributed.
* Capture is exception-gated everywhere: ``cost_analysis`` /
  ``memory_analysis`` availability drifts across jax versions and
  backends, and a telemetry failure must degrade, never break a
  launch.

Cost, stated honestly: with attribution on, a cold signature compiles
twice (once for the AOT capture, once through jit's own cache) — the
warm path never pays this.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "boundary",
    "boundaries",
    "boundary_fn",
    "configure",
    "reset",
    "enabled",
    "capture_enabled",
    "summary",
    "health_block",
    "dump",
    "dump_prewarm",
    "load_prewarm",
    "replay_records",
    "record_prewarm_compile",
    "capture_profile",
    "LEDGER_FILENAME",
    "PREWARM_FILENAME",
]

LEDGER_FILENAME = "devprof.json"

# the prewarm replay set: every (boundary, signature) the process ever
# launched, with an abstract (ShapeDtypeStruct) argument spec a future
# incarnation can replay through fn.lower(...).compile() — pickled
# because the specs carry real static objects (frozen CycleConfig);
# same trust domain as the persistent compile cache's executables
PREWARM_FILENAME = "prewarm.pkl"

# flush prewarm launch-count hotness every this many captured launches
# (a NEW signature always flushes immediately — losing one would leave
# a cold hole in the next incarnation's replay set)
_REPLAY_FLUSH_EVERY = 256

# signature strings are labels on events and ledger rows; a pathological
# static repr must not bloat them
_SIG_MAX = 160


def _now() -> float:
    return time.perf_counter()


class _Entry:
    """One (boundary, signature) row of the compile ledger."""

    __slots__ = (
        "boundary", "sig", "backend", "compile_ms", "flops",
        "bytes_accessed", "temp_bytes", "argument_bytes", "output_bytes",
        "first_seen_s",
    )

    def __init__(self, boundary: str, sig: str):
        self.boundary = boundary
        self.sig = sig
        self.backend: Optional[str] = None
        self.compile_ms: Optional[float] = None
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.temp_bytes: Optional[int] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.first_seen_s = time.time()

    def to_dict(self) -> dict:
        return {
            "boundary": self.boundary,
            "sig": self.sig,
            "backend": self.backend,
            "compile_ms": self.compile_ms,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "first_seen_s": self.first_seen_s,
        }


class _BoundaryStats:
    """Cumulative per-boundary launch/compile accounting."""

    __slots__ = ("launches", "compiles")

    def __init__(self):
        self.launches = 0
        self.compiles = 0

    def to_dict(self) -> dict:
        return {"launches": self.launches, "compiles": self.compiles}


class LaunchLedger:
    """Process-global registry of jit boundaries + their capture state.

    One instance lives at module scope (like the retrace-guard hook);
    tests get a fresh one via
    :func:`reset`.  All mutation happens under one lock — boundaries
    fire from the bridge worker threads AND the pipelined readback
    threads concurrently.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._boundaries: Dict[str, _BoundaryStats] = {}
        self._entries: Dict[tuple, _Entry] = {}  # (boundary, sig) -> row
        self._retraces: List[dict] = []  # attributed retrace events
        self.attribute = False  # compile attribution at cold launches
        self.capture = False  # prewarm replay-spec capture (ISSUE 20)
        self._metrics_ref: Optional[Callable[[], Any]] = None
        self.state_dir: Optional[str] = None
        # (boundary, sig) -> replay record: per-sig launch hotness plus
        # the pickled abstract argument spec (None = non-replayable)
        self._replays: Dict[tuple, dict] = {}
        self._replay_unflushed = 0

    # -- registration ------------------------------------------------
    def register(self, name: str) -> None:
        with self._lock:
            self._boundaries.setdefault(name, _BoundaryStats())

    def boundaries(self) -> List[str]:
        with self._lock:
            return sorted(self._boundaries)

    # -- configuration -----------------------------------------------
    def configure(self, attribute: Optional[bool] = None, metrics=None,
                  state_dir: Optional[str] = None,
                  capture: Optional[bool] = None) -> None:
        import weakref

        with self._lock:
            if attribute is not None:
                self.attribute = bool(attribute)
            if capture is not None:
                self.capture = bool(capture)
            if metrics is not None:
                # weakref, CycleTelemetry-feed style: the ledger is
                # process-global and must never pin a servicer's
                # metrics object past its lifetime
                self._metrics_ref = weakref.ref(metrics)
            if state_dir is not None:
                self.state_dir = str(state_dir)

    def _metrics(self):
        ref = self._metrics_ref
        if ref is None:
            return None
        return ref()

    # -- the wrapper's accounting primitives -------------------------
    def note_launch(self, name: str) -> None:
        with self._lock:
            st = self._boundaries.setdefault(name, _BoundaryStats())
            st.launches += 1

    def seen_sig(self, name: str, sig: str) -> bool:
        with self._lock:
            return (name, sig) in self._entries

    def record_compile(self, name: str, sig: str, backend: str,
                       compile_ms: float, cost: Optional[dict],
                       mem: Optional[dict]) -> None:
        with self._lock:
            st = self._boundaries.setdefault(name, _BoundaryStats())
            prior_sigs = st.compiles
            st.compiles += 1
            e = self._entries.setdefault((name, sig), _Entry(name, sig))
            e.backend = backend
            e.compile_ms = compile_ms
            if cost:
                e.flops = cost.get("flops")
                e.bytes_accessed = cost.get("bytes accessed")
            if mem:
                e.temp_bytes = mem.get("temp")
                e.argument_bytes = mem.get("argument")
                e.output_bytes = mem.get("output")
            retrace = prior_sigs > 0
            if retrace:
                # the attributed event the ISSUE asks for: not "a
                # cache miss happened" but "THIS boundary minted a new
                # program for THIS shape"
                self._retraces.append({
                    "boundary": name,
                    "sig": sig,
                    "backend": backend,
                    "compile_ms": compile_ms,
                    "at_s": time.time(),
                })
        m = self._metrics()
        if m is not None:
            try:
                m.devprof_compile(name, backend, compile_ms)
                if retrace:
                    m.devprof_retrace(name)
            except Exception:  # koordlint: disable=broad-except(reason: telemetry sink drift must not break a launch; the ledger itself already recorded the compile)
                pass
        self._flush(force=True)

    # -- prewarm replay capture (ISSUE 20) ---------------------------
    def note_replay(self, name: str, sig: str, args: tuple,
                    kwargs: dict) -> None:
        """Capture-mode accounting: bump the (boundary, sig) launch
        hotness; on first sight, record the abstract argument spec a
        future incarnation replays.  Spec pickling happens OUTSIDE the
        lock (statics can be arbitrarily slow to serialize); the
        double-checked insert keeps concurrent first-sights exact."""
        with self._lock:
            rec = self._replays.get((name, sig))
            if rec is not None:
                rec["launches"] += 1
                self._replay_unflushed += 1
                flush = self._replay_unflushed >= _REPLAY_FLUSH_EVERY
                if flush:
                    self._replay_unflushed = 0
            else:
                flush = False
        if rec is None:
            spec = _replay_spec_bytes(args, kwargs)
            with self._lock:
                rec = self._replays.setdefault((name, sig), {
                    "boundary": name,
                    "sig": sig,
                    "launches": 0,
                    "spec": spec,
                    "first_seen_s": time.time(),
                })
                rec["launches"] += 1
            flush = True  # a new signature flushes immediately
        if flush:
            self.dump_prewarm()

    def record_prewarm_compile(self, name: str, sig: str, backend: str,
                               compile_ms: float, cost: Optional[dict],
                               mem: Optional[dict]) -> None:
        """A compile the PREWARM thread performed: lands in the compile
        ledger like any other (so a later live launch of the same
        signature sees it warm and skips its own AOT capture), but is
        NOT an attributed retrace and feeds the ``prewarm_*`` metric
        families, not ``devprof_*`` — replaying yesterday's signatures
        is the expected boot path, not a shape-stability regression."""
        with self._lock:
            st = self._boundaries.setdefault(name, _BoundaryStats())
            st.compiles += 1
            e = self._entries.setdefault((name, sig), _Entry(name, sig))
            e.backend = backend
            e.compile_ms = compile_ms
            if cost:
                e.flops = cost.get("flops")
                e.bytes_accessed = cost.get("bytes accessed")
            if mem:
                e.temp_bytes = mem.get("temp")
                e.argument_bytes = mem.get("argument")
                e.output_bytes = mem.get("output")
        self._flush(force=True)

    def replay_records(self) -> List[dict]:
        """The captured replay set, ledger-hot order (most-launched
        first; ties break on name+sig for a deterministic replay)."""
        with self._lock:
            recs = [dict(r) for r in self._replays.values()]
        recs.sort(key=lambda r: (-r["launches"], r["boundary"], r["sig"]))
        return recs

    def load_replays(self, records: List[dict]) -> None:
        """Seed the capture set from a prior incarnation's prewarm file
        so re-dumps don't forget signatures this process never
        launched (counts merge additively on re-sight)."""
        with self._lock:
            for r in records:
                key = (r.get("boundary"), r.get("sig"))
                if key not in self._replays:
                    self._replays[key] = dict(r)

    def dump_prewarm(self, state_dir: Optional[str] = None) -> Optional[str]:
        """Write the replay set as ``<state-dir>/prewarm.pkl``.
        Returns the path, or None without a state dir."""
        import pickle

        target = state_dir or self.state_dir
        if not target:
            return None
        path = os.path.join(target, PREWARM_FILENAME)
        doc = {"version": 1, "records": self.replay_records()}
        tmp = path + ".tmp"
        try:
            os.makedirs(target, exist_ok=True)
            with open(tmp, "wb") as fh:
                pickle.dump(doc, fh)
            os.replace(tmp, path)
        except OSError:
            return None
        return path

    # -- views -------------------------------------------------------
    def summary(self) -> dict:
        """The bench/report view: compile ledger + per-boundary launch
        and compile counts + attributed retraces."""
        with self._lock:
            entries = [e.to_dict() for e in self._entries.values()]
            bounds = {
                n: st.to_dict() for n, st in self._boundaries.items()
            }
            retraces = list(self._retraces)
            attribute = self.attribute
        entries.sort(key=lambda d: (d["boundary"], d["sig"]))
        return {
            "attribute": attribute,
            "backend": _backend_platform(),
            "boundaries": bounds,
            "entries": entries,
            "retraces": retraces,
        }

    def health_block(self) -> dict:
        """The /healthz ``device`` block: platform, device count and
        the compile ledger summary."""
        with self._lock:
            compiles = sum(st.compiles for st in self._boundaries.values())
            compile_ms = sum(
                e.compile_ms or 0.0 for e in self._entries.values()
            )
            retraces = len(self._retraces)
            attribute = self.attribute
            registered = len(self._boundaries)
        return {
            "platform": _backend_platform(),
            "device_count": _device_count(),
            "attribute": attribute,
            "registered_boundaries": registered,
            "compiles": compiles,
            "compile_ms_total": round(compile_ms, 3),
            "retraces": retraces,
        }

    # -- persistence -------------------------------------------------
    def dump(self, state_dir: Optional[str] = None) -> Optional[str]:
        """Write the ledger as ``<state-dir>/devprof.json`` (the report
        CLI's input).  Returns the path, or None without a state dir."""
        target = state_dir or self.state_dir
        if not target:
            return None
        path = os.path.join(target, LEDGER_FILENAME)
        doc = self.summary()
        tmp = path + ".tmp"
        try:
            os.makedirs(target, exist_ok=True)
            with open(tmp, "w") as fh:
                json.dump(doc, fh, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            return None
        return path

    def _flush(self, force: bool = False) -> None:
        if self.state_dir:
            self.dump()


# -- module-level singleton ------------------------------------------

_LEDGER = LaunchLedger()

# boundary name -> the jitted callable the decorator wrapped.  Module
# scope (NOT ledger state): decoration happens once per import, and a
# test's reset() must not orphan the prewarm runner's name->fn
# resolution.  Latest registration wins (module reloads).
_BOUNDARY_FNS: Dict[str, Any] = {}


def _ledger() -> LaunchLedger:
    return _LEDGER


def reset() -> None:
    """Fresh ledger (tests).  Boundaries re-register lazily on their
    next launch; already-wrapped callables keep working because the
    wrapper resolves the singleton per call."""
    global _LEDGER
    _LEDGER = LaunchLedger()


def configure(attribute: Optional[bool] = None, metrics=None,
              state_dir: Optional[str] = None,
              capture: Optional[bool] = None) -> None:
    _LEDGER.configure(attribute=attribute, metrics=metrics,
                      state_dir=state_dir, capture=capture)


def enabled() -> bool:
    """Whether compile attribution is on."""
    return _LEDGER.attribute


def capture_enabled() -> bool:
    return _LEDGER.capture


def boundaries() -> List[str]:
    return _LEDGER.boundaries()


def boundary_fn(name: str) -> Optional[Any]:
    """The jitted callable registered under ``name`` (its ``.lower``
    AOT seam is the prewarm replay target), or None when the defining
    module has not been imported in this process."""
    return _BOUNDARY_FNS.get(name)


def replay_records() -> List[dict]:
    return _LEDGER.replay_records()


def dump_prewarm(state_dir: Optional[str] = None) -> Optional[str]:
    return _LEDGER.dump_prewarm(state_dir)


def load_prewarm(state_dir: str) -> List[dict]:
    """Read ``<state-dir>/prewarm.pkl`` -> replay records, ledger-hot
    order.  Missing/corrupt files are an empty replay set — prewarm is
    an accelerant, never a boot dependency."""
    import pickle

    path = os.path.join(state_dir, PREWARM_FILENAME)
    try:
        with open(path, "rb") as fh:
            doc = pickle.load(fh)
    except Exception:  # koordlint: disable=broad-except(reason: a missing, torn or version-drifted prewarm file must degrade to a cold boot, never block one)
        return []
    records = doc.get("records") if isinstance(doc, dict) else None
    if not isinstance(records, list):
        return []
    out = [
        r for r in records
        if isinstance(r, dict) and r.get("boundary") and r.get("sig")
    ]
    out.sort(key=lambda r: (-int(r.get("launches") or 0),
                            r["boundary"], r["sig"]))
    return out


def record_prewarm_compile(name: str, sig: str, backend: str,
                           compile_ms: float, cost: Optional[dict],
                           mem: Optional[dict]) -> None:
    _LEDGER.record_prewarm_compile(name, sig, backend, compile_ms,
                                   cost, mem)


def load_replays(records: List[dict]) -> None:
    _LEDGER.load_replays(records)


def summary() -> dict:
    return _LEDGER.summary()


def health_block() -> dict:
    return _LEDGER.health_block()


def dump(state_dir: Optional[str] = None) -> Optional[str]:
    return _LEDGER.dump(state_dir)


# -- environment probes (exception-gated; jax import stays lazy) -----

def _backend_platform() -> Optional[str]:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # koordlint: disable=broad-except(reason: environment probe — no jax / no backend means no platform to report, never an error)
        return None


def _device_count() -> Optional[int]:
    try:
        import jax

        return jax.device_count()
    except Exception:  # koordlint: disable=broad-except(reason: environment probe — no jax / no backend means no device count to report, never an error)
        return None


def _trace_state_clean() -> bool:
    """True when no jax trace is live on this thread (public jax.core
    probe).  No exception gate: a probe that stops existing must fail
    loudly, not claim "clean" and time every nested boundary as a
    launch."""
    import jax

    return not jax.core.unsafe_am_i_under_a_jit_DO_NOT_USE()


# -- signatures ------------------------------------------------------

def _leaf_sig(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    r = repr(leaf)
    if len(r) > 40:
        r = r[:37] + "..."
    return r


def shape_signature(args: tuple, kwargs: dict) -> str:
    """The static shape signature keying the compile ledger: dtype[shape]
    per array leaf (pytrees flattened), short reprs for statics — the
    same partition jit's own cache keys on, rendered human-readable so a
    retrace event names the shape that minted it."""
    from jax.tree_util import tree_leaves

    parts = [_leaf_sig(leaf) for leaf in tree_leaves((args, kwargs))]
    sig = ";".join(parts)
    if len(sig) > _SIG_MAX:
        import hashlib

        digest = hashlib.sha1(sig.encode()).hexdigest()[:8]
        sig = sig[: _SIG_MAX - 12] + "...#" + digest
    return sig


# -- prewarm replay specs --------------------------------------------

def _replay_spec_bytes(args: tuple, kwargs: dict) -> Optional[bytes]:
    """Pickle an ABSTRACT copy of a launch's arguments: array leaves
    become ``jax.ShapeDtypeStruct`` (shape/dtype/weak_type — exactly
    what ``fn.lower`` needs to mint the same program), statics ride
    as-is.  None = non-replayable (a process-local static like a Mesh
    refuses pickling); the launch itself is never at risk."""
    try:
        import pickle

        import jax
        from jax.tree_util import tree_map

        def leaf(x):
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is not None and dtype is not None:
                return jax.ShapeDtypeStruct(
                    shape, dtype,
                    weak_type=bool(getattr(x, "weak_type", False)),
                )
            return x

        return pickle.dumps(tree_map(leaf, (args, dict(kwargs))))
    except Exception:  # koordlint: disable=broad-except(reason: an unpicklable static (Mesh, callables) marks the signature non-replayable; capture degrades, the launch is unaffected)
        return None


# -- AOT capture -----------------------------------------------------

def _cost_dict(compiled) -> Optional[dict]:
    try:
        ca = compiled.cost_analysis()
    except Exception:  # koordlint: disable=broad-except(reason: cost_analysis availability drifts across jax versions/backends; attribution degrades to None, the launch is unaffected)
        return None
    if isinstance(ca, (list, tuple)):  # per-device list on some versions
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key in ("flops", "bytes accessed"):
        v = ca.get(key)
        if isinstance(v, (int, float)):
            out[key] = float(v)
    return out


def _mem_dict(compiled) -> Optional[dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:  # koordlint: disable=broad-except(reason: memory_analysis availability drifts across jax versions/backends; attribution degrades to None, the launch is unaffected)
        return None
    out = {}
    for key, attr in (
        ("temp", "temp_size_in_bytes"),
        ("argument", "argument_size_in_bytes"),
        ("output", "output_size_in_bytes"),
    ):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            out[key] = int(v)
    return out or None


def _aot_capture(led: LaunchLedger, name: str, sig: str, fn,
                 args: tuple, kwargs: dict) -> Optional[float]:
    """First-compile capture through the AOT path.  Returns compile
    wall-time ms, or None when the boundary refuses AOT (abstract
    tracing can reject what the concrete call accepts — e.g. a
    non-hashable static); the launch itself is never at risk."""
    try:
        t0 = _now()
        compiled = fn.lower(*args, **kwargs).compile()
        compile_ms = (_now() - t0) * 1e3
    except Exception:  # koordlint: disable=broad-except(reason: AOT lowering can reject what the concrete call accepts (non-hashable statics); the boundary then runs unattributed rather than failing the launch)
        return None
    led.record_compile(
        name, sig, _backend_platform() or "unknown", compile_ms,
        _cost_dict(compiled), _mem_dict(compiled),
    )
    return compile_ms


# -- the decorator ---------------------------------------------------

def boundary(name: str):
    """Register a jit boundary with the launch ledger.

    Stacks ABOVE the jit application (decorators apply bottom-up), so
    the wrapper holds the jitted callable and its ``.lower`` AOT seam::

        @devprof.boundary("solver.greedy.score_cycle")
        @partial(jax.jit, static_argnames=("cfg",))
        def score_cycle(snapshot, *, cfg): ...

    Off (attribution and prewarm capture off): one comparison then
    tail-call — the warm stream is bit-identical with zero retraces
    (the tier-1 retrace-guard oracles run this path).  Inside a live
    jax trace the wrapper also steps aside: nested boundaries
    (``score_cycle`` under the Pallas cycle, term extras inside
    ``score_all``) are attributed at their outermost host callsite
    only.  With prewarm capture ON (``--prewarm``, ISSUE 20) every
    outermost launch additionally records its (boundary, signature)
    and an abstract replay spec for the next incarnation's prewarm
    thread.
    """

    def deco(fn):
        _LEDGER.register(name)
        _BOUNDARY_FNS[name] = fn

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            led = _LEDGER
            if not led.attribute and not led.capture:
                return fn(*args, **kwargs)  # bit-inert fast path
            if not _trace_state_clean():
                return fn(*args, **kwargs)  # nested under another jit
            if led.capture:
                try:
                    led.note_replay(
                        name, shape_signature(args, kwargs), args, kwargs
                    )
                except Exception:  # koordlint: disable=broad-except(reason: replay capture is an accelerant — an exotic pytree costs the prewarm record, never the launch)
                    pass
                if not led.attribute:
                    return fn(*args, **kwargs)
            led.note_launch(name)
            try:
                sig = shape_signature(args, kwargs)
                cold = not led.seen_sig(name, sig)
            except Exception:  # koordlint: disable=broad-except(reason: an unhashable/exotic pytree must cost attribution, never the launch — fall through to the plain call)
                return fn(*args, **kwargs)
            if cold:
                _aot_capture(led, name, sig, fn, args, kwargs)
            return fn(*args, **kwargs)

        wrapper.__wrapped__ = fn
        wrapper.devprof_boundary = name
        return wrapper

    return deco


# -- on-demand profiler capture (admin plane) ------------------------

def capture_profile(state_dir: str, window_ms: int = 1000) -> str:
    """Start a ``jax.profiler`` trace window under ``state_dir`` and
    stop it after ``window_ms`` on a background thread — the admin-RPC
    seam (udsserver METHOD_PROFILE) returns the capture directory
    immediately; inspection happens offline.  The trace holds the
    device operations and the ``koord.*`` host stages (obs/spans.py)
    on one clock."""
    import jax

    out_dir = os.path.join(
        state_dir, "devprof_trace", f"capture-{os.getpid()}-{time.time_ns()}"
    )
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)

    def _stop():
        time.sleep(max(0, int(window_ms)) / 1e3)
        try:
            jax.profiler.stop_trace()
        except Exception:  # koordlint: disable=broad-except(reason: double-stop / backend teardown races are admin-plane noise, not daemon faults)
            pass

    threading.Thread(target=_stop, daemon=True, name="devprof-capture").start()
    return out_dir


# -- report CLI ------------------------------------------------------

def _fmt_num(v, scale=1.0, suffix="") -> str:
    if v is None:
        return "-"
    return f"{v / scale:,.1f}{suffix}"


def format_report(doc: dict) -> str:
    """Render a dumped ledger: the compile ledger (one row per
    boundary+signature with compile ms / flops / bytes) and the
    attributed retraces."""
    lines = []
    backend = doc.get("backend") or "unknown"
    lines.append(
        f"devprof ledger — backend={backend} "
        f"attribute={doc.get('attribute')}"
    )
    lines.append("")
    lines.append("compile ledger:")
    header = (
        f"  {'boundary':<44} {'compile_ms':>10} {'flops':>12} "
        f"{'bytes':>12} {'temp_b':>10}  sig"
    )
    lines.append(header)
    for e in doc.get("entries", []):
        lines.append(
            f"  {e['boundary']:<44} "
            f"{_fmt_num(e.get('compile_ms')):>10} "
            f"{_fmt_num(e.get('flops')):>12} "
            f"{_fmt_num(e.get('bytes_accessed')):>12} "
            f"{_fmt_num(e.get('temp_bytes')):>10}  {e.get('sig', '')}"
        )
    if not doc.get("entries"):
        lines.append("  (no compiles captured)")
    retraces = doc.get("retraces", [])
    if retraces:
        lines.append("")
        lines.append(f"attributed retraces ({len(retraces)}):")
        for r in retraces:
            lines.append(
                f"  {r['boundary']}  +{_fmt_num(r.get('compile_ms'))} ms"
                f"  sig={r.get('sig', '')}"
            )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m koordinator_tpu.obs.devprof",
        description="Print the XLA launch ledger captured under a "
        "daemon's --state-dir (compile costs and attributed retraces).",
    )
    ap.add_argument("state_dir", help="daemon --state-dir (or any "
                    f"directory holding {LEDGER_FILENAME})")
    args = ap.parse_args(argv)
    path = args.state_dir
    if os.path.isdir(path):
        path = os.path.join(path, LEDGER_FILENAME)
    if not os.path.exists(path):
        print(f"devprof: no ledger at {path} (turn attribution on with "
              "devprof.configure(attribute=True), or call "
              "devprof.dump())", file=sys.stderr)
        return 2
    with open(path) as fh:
        doc = json.load(fh)
    print(format_report(doc))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via main() in tests
    raise SystemExit(main())
