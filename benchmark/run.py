#!/usr/bin/env python3
"""One run of one benchmark cell, in one process that holds the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

Set-up generates the cell's cluster from ``--seed``, starts the
scheduler daemon (``SchedulerServer``) in-process on a UDS under
``$TMPDIR``, and warms, through the loop itself, every program the
cell's traffic runs: a fixed number of cycles, then every scatter
bucket near those they met (``Loop.warm_buckets``).  Then it runs the closed loop for ``--seconds``
through ``ScorerClient`` (Sync, then Assign or ScoreFlat), compares the
window's answers with the plain reference, and prints the result as the
last line of stdout: one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and the numbers compared with their limits under ``checks``, last).
The numbers compared also end stderr, one per line.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  JAX's compilation cache is kept at
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _since_process_start() -> float:
    """Seconds from this process's start to now (Linux /proc)."""
    try:
        with open("/proc/self/stat") as fh:
            fields = fh.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return max(0.0, uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


PROCESS_START = _T_IMPORT - _since_process_start()

# warm-up cycles before the window, the same number in every run
WARM_CYCLES = 12


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class CompileWatch:
    """XLA compiles (count, seconds; a program loaded from the persistent
    cache counts too) and persistent-cache hits, read off JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            self.names.append(fun_name)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class StallWatch:
    """Diagnostic for the window's stalls (``--stallwatch``; off in the
    driver's runs).  A thread wakes every 50 ms; where a wake comes
    200 ms or more late, it notes when, the process's CPU seconds, the
    host's steal and iowait over the gap, and the threads that used the
    most CPU since its last look (at most 250 ms before the gap)."""

    TICK, LATE, LOOK = 0.05, 0.2, 0.25

    def __init__(self, t0_ref):
        import threading

        self.t0 = t0_ref
        self.events = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stallwatch")
        self._thread.start()

    @staticmethod
    def host_stat():
        """Host-wide (steal, iowait) jiffies from /proc/stat."""
        with open("/proc/stat") as fh:
            f = fh.readline().split()
        return int(f[8]), int(f[5])

    @staticmethod
    def threads():
        """{tid: (name, cpu jiffies)} of this process's threads."""
        out = {}
        for tid in os.listdir("/proc/self/task"):
            try:
                with open(f"/proc/self/task/{tid}/stat") as fh:
                    st = fh.read()
            except OSError:
                continue
            name = st[st.index("(") + 1:st.rindex(")")]
            f = st.rsplit(")", 1)[1].split()
            out[int(tid)] = (name, int(f[11]) + int(f[12]))
        return out

    def _run(self):
        import threading

        last = time.perf_counter()
        cpu, stat = time.process_time(), self.host_stat()
        look_t, look = last, self.threads()
        while not self._stop.wait(self.TICK):
            now = time.perf_counter()
            late = now - last - self.TICK
            if late >= self.LATE:
                cpu2, stat2, th = time.process_time(), self.host_stat(), \
                    self.threads()
                names = {t.native_id: t.name for t in threading.enumerate()}
                top = sorted(((th[k][1] - look.get(k, ("", 0))[1],
                               names.get(k, th[k][0])) for k in th),
                             reverse=True)[:4]
                self.events.append({
                    "at_s": round(last + self.TICK - self.t0[0], 3),
                    "late_ms": round(late * 1e3, 1),
                    "cpu_ms": round((cpu2 - cpu) * 1e3, 1),
                    "steal_j": stat2[0] - stat[0],
                    "iowait_j": stat2[1] - stat[1],
                    "since_look_ms": round((now - look_t) * 1e3, 1),
                    "top_threads_j": [[j, n] for j, n in top if j]})
            last, cpu, stat = now, time.process_time(), self.host_stat()
            if now - look_t >= self.LOOK:
                look_t, look = now, self.threads()

    def stop(self):
        self._stop.set()
        self._thread.join()


class Run:
    """What a metric reader reads: the window's cycle records, its
    length, the set-up time and, in a traced run, the reduced trace."""

    def __init__(self, cell, records, window_s, setup_s, trace=None):
        self.rpc = cell.traffic["rpc"]
        self.records = records
        self.window_s = window_s
        self.setup_s = setup_s
        self.trace = trace


def start_server(workdir):
    from koordinator_tpu.bridge.client import ScorerClient
    from koordinator_tpu.scheduler.server import SchedulerServer

    sock = os.path.join(workdir, "scorer.sock")
    server = SchedulerServer(
        lease_path=os.path.join(workdir, "leader.lease"),
        uds_path=sock,
        state_dir=os.path.join(workdir, "state"),
    ).start()
    deadline = time.monotonic() + 60
    while not server.elector.is_leader and time.monotonic() < deadline:
        time.sleep(0.02)
    if not server.elector.is_leader:
        server.stop()
        raise RuntimeError("the scheduler never took its lease")
    return server, ScorerClient(f"unix://{sock}")


def memo_hits(server) -> float:
    reg = server.servicer.telemetry.metrics.registry
    return sum(reg.get(name, {"result": "hit"}) or 0.0 for name in (
        "koord_scorer_assign_memo_total", "koord_scorer_score_memo_total"))


def _io_bytes():
    """This process's read and write counters (/proc/self/io)."""
    try:
        with open("/proc/self/io") as fh:
            return {k: int(v) for k, v in
                    (line.split(":") for line in fh if ":" in line)}
    except OSError:
        return {}


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # host: the benchmark's own spans and the runtime's main events, no
    # Python function tracing (it would slow the host path it measures)
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def reduce_trace(tdir, kernel_pattern):
    """Busy time, window, top operations and idle gaps of the traced
    window, from the trace's own clock."""
    from benchmark.harness import tracefile as tf

    path = tf.find_xplane(tdir)
    if path is None:
        return None
    devices, modules, host = tf.load(path)
    window = [s for s in host if s[2] == "bench.window"]
    if not window or not devices:
        return None
    lo, hi = window[0][0], window[0][1]
    spans = [s for s in host if s[2] != "bench.window"]
    # averaged over the chips used: those that ran an operation in the
    # window (a cell that holds four chips may drive one)
    busy = [b for b in (tf.busy_ns(evs, lo, hi) for evs in devices.values())
            if b > 0] or [0.0]
    ops = [e for evs in devices.values() for e in evs]
    modules = [e for evs in modules.values() for e in evs]
    kern_ns, kern_n = tf.kernel_ns(ops, kernel_pattern, lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "kernel_s": kern_ns / 1e9,
        "kernel_events": kern_n,
        "device_ops": tf.top_ops(tf.named_by_module(ops, modules), lo, hi),
        "idle_gaps": tf.idle_gaps(ops, spans, lo, hi),
    }


def run_cell(cell, seed: int, seconds: float, trace: bool,
             control: bool = False, stallwatch: bool = False, out=print):
    """Set-up, window, check.  Returns the result object."""
    import gc

    import grpc
    import jax
    import numpy as np

    from benchmark.harness.check import CHECKS, LIMITS
    from benchmark.harness.loop import Loop, _annotate
    from benchmark.harness.spec import read_metrics

    traffic = cell.traffic
    # set-up's phases, in seconds from the process's start; "imported"
    # holds Python's, JAX's and the TPU runtime's start and the imports
    phases = {"imported": time.perf_counter() - PROCESS_START}
    loop = Loop(cell.config, traffic, seed)
    check = CHECKS[loop.rpc](np.random.default_rng([seed % 2**63, 2]),
                             traffic.get("check_cycles", 0), control)
    device = jax.devices()[0]
    watch = CompileWatch()
    phases["cluster"] = time.perf_counter() - PROCESS_START
    workdir = tempfile.mkdtemp(prefix="koordbench-")
    tdir = os.path.join(workdir, "trace")
    server = client = stall = None
    records, failed = [], 0
    try:
        server, client = start_server(workdir)
        phases["server"] = time.perf_counter() - PROCESS_START
        # warm-up: the loop's own cycles, a fixed number, then every
        # scatter bucket near those they met, then two more cycles
        for i in range(WARM_CYCLES):
            before = watch.compiles
            rec = loop.cycle(client)
            if watch.compiles > before or i < 2:
                out(f"warm cycle={i} cycle_ms={rec['cycle_ms']:.1f} "
                    f"compiles={watch.names[before:]} "
                    f"placed={rec['placed']} path={rec.get('path', '-')}",
                    flush=True)
        phases["cycles"] = time.perf_counter() - PROCESS_START
        before = watch.compiles
        warmed = loop.warm_buckets(client)
        out(f"warm buckets={warmed} compiles={watch.compiles - before}",
            flush=True)
        for i in range(2):
            loop.cycle(client)
        phases["buckets"] = time.perf_counter() - PROCESS_START
        compiles0, hits0 = watch.compiles, watch.cache_hits
        memo0 = memo_hits(server)
        note = _annotate(trace)
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        t0_ref = [time.perf_counter()]
        if stallwatch:
            stall = StallWatch(t0_ref)

        def keep(state):
            check.keep(loop, state)

        # the benchmark and the daemon share this process: freeze what
        # set-up left alive (the modules, JAX, the cluster, the compiled
        # programs) so that Python's full collections in the window scan
        # only what the window allocates, as in a daemon of its own
        gc.collect()
        gc.freeze()
        t0 = t0_ref[0] = time.perf_counter()
        setup_s = t0 - PROCESS_START
        with note("bench.window"):
            while True:
                try:
                    rec = loop.cycle(client, note, keep)
                except grpc.RpcError as exc:
                    failed += 1
                    loop.answer = None
                    rec = {"error": str(exc.code()), "digest": None}
                    log(f"request failed: {exc.code()} {exc.details()}")
                check.answer(loop, rec)
                if "error" not in rec:
                    records.append(rec)
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        gc.unfreeze()
        if stall is not None:
            stall.stop()
        if trace:
            jax.profiler.stop_trace()
        compiles_in_window = watch.compiles - compiles0
        # a program met first inside the window and found in the
        # persistent cache is loaded, not compiled
        loads_in_window = watch.cache_hits - hits0
        memo_in_window = memo_hits(server) - memo0
        # the fullest chip's peak
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0
                    for d in jax.devices()), default=0) or None
    finally:
        if client is not None:
            client.close()
        if server is not None:
            server.stop()
    paths = sorted({r.get("path", "-") for r in records})
    out(f"window cycles={len(records)} failed={failed} "
        f"window_s={window_s:.3f} setup_s={setup_s:.3f} "
        f"placed={sum(r['placed'] for r in records)} paths={paths} "
        f"compiles_in_window={compiles_in_window - loads_in_window} "
        f"cache_loads_in_window={loads_in_window} "
        f"{watch.names[compiles0:compiles0 + compiles_in_window]} "
        f"memo_hits_in_window={memo_in_window:g} "
        f"compile_s_total={watch.compile_s:.2f} "
        f"cache_hits={watch.cache_hits}", flush=True)
    means = {k: sum(r[k] for r in records) / len(records)
             for k in ("binder_ms", "sync_ms", "rpc_ms", "cycle_ms")
             if records}
    out("window_means_ms " + json.dumps(means), flush=True)
    out("setup_phases_s " + json.dumps(
        {k: round(v, 3) for k, v in phases.items()}), flush=True)
    # binder, Sync, request and the process's CPU time over the cycle
    slow = sorted(records, key=lambda r: -(r["binder_ms"] + r["cycle_ms"]))
    out("slowest_cycles_ms " + json.dumps(
        [[round(r["start"] - t0, 3), round(r["binder_ms"], 1),
          round(r["sync_ms"], 1), round(r["rpc_ms"], 1),
          round(r["cpu_ms"], 1)] for r in slow[:6]]), flush=True)
    if stall is not None:
        out("stalls " + json.dumps(stall.events), flush=True)
    # cycles started in each second of the window, and the bytes this
    # process has written (the driver counts a machine's writes)
    bins = [0] * (int(window_s) + 1)
    for r in records:
        bins[min(int(r["start"] - t0), len(bins) - 1)] += 1
    out("cycles_per_second " + json.dumps(bins), flush=True)
    out("io " + json.dumps(_io_bytes()), flush=True)
    reduced = None
    if trace:
        reduced = reduce_trace(tdir, cell.config["kernel_pattern"])
        if reduced is not None:
            out("trace " + json.dumps(reduced), flush=True)
    shutil.rmtree(workdir, ignore_errors=True)

    verdict = check.finish(cell.config["scoring"])
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in verdict["numbers"].items()}
    out(f"check_info {json.dumps(verdict['info'])}", flush=True)
    checked = verdict["info"]["checked_cycles"]
    correct = (failed == 0 and checked > 0 and records != [] and
               all(c["value"] <= c["limit"] for c in checks.values()))

    run = Run(cell, records, window_s, setup_s, reduced)
    kind = "per_layer" if trace else "end_to_end"
    result = {
        "correct": bool(correct),
        "attempted": len(records) + failed,
        "failed": failed,
        "metrics": read_metrics(cell, kind, run),
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the control: the reference on the state before "
                    "each Sync in the program's place; must read not "
                    "correct.  Not part of a driver run")
    ap.add_argument("--stallwatch", action="store_true",
                    help="diagnostic: note the window's stalls")
    args = ap.parse_args(argv)

    from benchmark.harness.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    # the program takes the compilation cache the benchmark gives it;
    # JAX writes no entry into a directory that does not exist
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    # every program goes into the cache, however fast it compiled: the
    # Sync's scatter programs (under a second each) come in buckets of
    # the changed cells, and a bucket first met inside the window would
    # otherwise compile there in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"no result: {cell.name} needs {cell.chips} TPU chip(s), JAX "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return 3
    import koordinator_tpu  # noqa: F401  (x64 before any array exists)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=args.control, stallwatch=args.stallwatch)
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
