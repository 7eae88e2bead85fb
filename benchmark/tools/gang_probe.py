#!/usr/bin/env python3
"""What the program does with Coscheduling gangs, against the Strict
reference: one ``run.run_cell`` run of a gang cell whose check keeps up
to ``--cycles`` of the window's cycles (reservoir-sampled, as the
benchmark's check samples its two) and reads, for each, the reference's
own count of gangs that failed partway (placed a member, then undone).

    python3 benchmark/tools/gang_probe.py --root <dir> --workload <name>
        --seed <n> --seconds <s> [--cycles 20] [--nodes N] [--backlog B]

``--root`` holds a ``BENCHMARK.json`` that names the cell and the
configuration file; the traffic mix comes from ``benchmark/traffic``.
``--nodes`` and ``--backlog`` cut the cell to a size the CPU can run.
On the CPU it runs as the benchmark's tests do; on a TPU, as run.py.
Prints run.py's result line, then ``gangs {...}``, last: per cycle
checked, the bound answers that differ and the gangs the reference
undid partway, and in all the share of cycles with such a gang.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import check, reference  # noqa: E402
from benchmark.harness.spec import load_cell  # noqa: E402


def probe_check(cycles: int, report: dict):
    class ProbeCheck(check.AssignCheck):
        def __init__(self, gen, sample, control=False):
            super().__init__(gen, cycles, control)

        def finish(self, scoring):
            rows = []
            for state, _prev, got in self.slots:
                if got is None:
                    continue
                t0 = time.perf_counter()
                want, partway, _ = reference.run(state, scoring)
                rows.append({"mismatch": int((want != got).sum()),
                             "partway": partway,
                             "reference_s": time.perf_counter() - t0,
                             "bound_program": int((got >= 0).sum()),
                             "bound_reference": int((want >= 0).sum())})
            report["cycles"] = rows
            report["share_partway"] = (
                sum(r["partway"] > 0 for r in rows) / len(rows)
                if rows else None)
            return super().finish(scoring)

    return ProbeCheck


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cycles", type=int, default=20)
    ap.add_argument("--nodes", type=int)
    ap.add_argument("--backlog", type=int)
    args = ap.parse_args(argv)

    cell = load_cell(os.path.abspath(args.root), args.workload)
    if args.nodes:
        cell.config["nodes"]["count"] = args.nodes
    if args.backlog:
        cell.traffic = dict(cell.traffic, backlog_pods=args.backlog)
    os.makedirs(run.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import koordinator_tpu  # noqa: F401  (x64 before any array exists)

    report = {}
    check.CHECKS["assign"] = probe_check(args.cycles, report)
    result = run.run_cell(cell, args.seed, args.seconds, False,
                          out=lambda *a, **k: print(*a, file=sys.stderr,
                                                    **k))
    print(json.dumps(result), flush=True)
    print("gangs " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
