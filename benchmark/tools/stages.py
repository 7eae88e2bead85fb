#!/usr/bin/env python3
"""One run of one cell, as benchmark/run.py makes it, that also reads
the program's own stages.

    python3 benchmark/tools/stages.py --workload <name> --seed <n>
                                      --seconds <s> --trace <0|1>

The run is ``run.run_cell`` itself.  With ``--trace 1`` the same trace
is also reduced by ``harness/stages.py``: the per-cycle means of the
``koord.*`` stages the daemon annotates, how much of ``koord.sync`` and
``koord.assign`` their children cover, the device's idle time charged
span by span (``idle_spans``), and how much of the idle inside the
client's Sync and Assign calls the stages name.  Every run also reads
the Sync requests' bytes in the window (the daemon's
``koord_scorer_rpc_request_bytes_total``) and the cycle time.  Prints
run.py's result line, then one line ``stages {...}``, last.  On a
program without the annotations or the counter those numbers are
absent.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import stages as st  # noqa: E402
from benchmark.harness import tracefile as tf  # noqa: E402

SYNC_BYTES = ("koord_scorer_rpc_request_bytes_total", {"method": "sync"})


def _reduce_stages(tdir: str) -> dict:
    path = tf.find_xplane(tdir)
    if path is None:
        return {}
    spans = st.host_spans(path)
    window = [s for s in spans if s[2] == st.WINDOW]
    if not window:
        return {}
    lo, hi = window[0][0], window[0][1]
    devices, _modules, _host = tf.load(path)
    ops = [e for evs in devices.values() for e in evs]
    stats = st.span_stats(spans, lo, hi)
    cycles = max(stats.get("bench.assign", [0])[0],
                 stats.get("bench.score", [0])[0])
    inside, named = st.rpc_idle_named(ops, spans, lo, hi)
    return {
        "trace_cycles": cycles,
        "stage_ms": st.stage_ms(stats, cycles),
        "coverage": st.coverage(stats),
        "idle_spans": st.idle_spans(ops, spans, lo, hi),
        "rpc_idle_s": inside,
        "rpc_idle_named_s": named,
        "spans": {k: v for k, v in sorted(stats.items())
                  if k.startswith("koord.")},
    }


def measure(cell, seed: int, seconds: float, trace: bool, out=print):
    """``run.run_cell`` with the stage readers attached; returns
    (result, stages)."""
    extra: dict = {}
    counts = []
    lines = []
    stock_reduce, stock_memo = run.reduce_trace, run.memo_hits

    def reduce_trace(tdir, kernel_pattern):
        reduced = stock_reduce(tdir, kernel_pattern)
        extra.update(_reduce_stages(tdir))
        return reduced

    def memo_hits(server):
        # read at the window's start and end, as run.py reads the memo
        reg = server.servicer.telemetry.metrics.registry
        counts.append(reg.get(*SYNC_BYTES))
        return stock_memo(server)

    def capture(*parts, **kw):
        lines.append(" ".join(str(p) for p in parts))
        out(*parts, **kw)

    run.reduce_trace, run.memo_hits = reduce_trace, memo_hits
    try:
        result = run.run_cell(cell, seed, seconds, trace, out=capture)
    finally:
        run.reduce_trace, run.memo_hits = stock_reduce, stock_memo
    stages = dict(extra)
    for line in lines:
        m = re.match(r"window cycles=(\d+) failed=\d+ window_s=([\d.]+)",
                     line)
        if m:
            cycles, window_s = int(m.group(1)), float(m.group(2))
            stages.update(cycles=cycles, window_s=window_s,
                          cycle_ms=window_s / cycles * 1e3 if cycles else None)
            if len(counts) >= 2 and None not in counts[-2:] and cycles:
                stages["sync_wire_kib"] = (
                    (counts[-1] - counts[-2]) / 1024.0 / cycles)
        elif line.startswith("window_means_ms "):
            stages["means_ms"] = json.loads(line.split(" ", 1)[1])
    return result, stages


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from benchmark.harness.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    os.makedirs(run.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        run.log(f"no result: {cell.name} needs {cell.chips} TPU chip(s)")
        return 3
    import koordinator_tpu  # noqa: F401  (x64 before any array exists)

    result, stages = measure(cell, args.seed, args.seconds,
                             bool(args.trace))
    print(json.dumps(result), flush=True)
    print("stages " + json.dumps(dict(
        stages, workload=cell.name, seed=args.seed, trace=args.trace)),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
