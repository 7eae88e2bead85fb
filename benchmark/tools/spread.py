"""Spread of each metric over sets of runs, as the bounds are set.

    python benchmark/tools/spread.py <set files...>

Each file holds the result lines (the last stdout line of each run) of
one set, one per line.  Prints per set and metric the median, the
quartiles of ``statistics.quantiles(values, n=4)``, the spread (their
distance over the median) and, per metric, the wider of the sets'
spreads and five times it: the bound it would give.
"""

import json
import statistics
import sys


def spread(vals):
    q1, _, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / statistics.median(vals)


def trimmed(vals):
    """The set less its run farthest from the median."""
    med = statistics.median(vals)
    far = max(range(len(vals)), key=lambda i: abs(vals[i] - med))
    return vals[:far] + vals[far + 1:]


def spreads(path):
    values = {}
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line.startswith("{"):
                continue
            res = json.loads(line)
            for name, m in res["metrics"].items():
                values.setdefault(name, []).append(m["value"])
    out = {}
    for name, vals in values.items():
        med = statistics.median(vals)
        q1, _, q3 = statistics.quantiles(vals, n=4)
        out[name] = (len(vals), med, q1, q3, (q3 - q1) / med if med else 0,
                     spread(trimmed(vals)), vals)
    return out


def main(paths):
    widest, trim, medians, every = {}, {}, {}, {}
    for path in paths:
        for name, (n, med, q1, q3, sp, tsp, vals) in sorted(
                spreads(path).items()):
            print(f"{path} {name}: n={n} median={med!r} q1={q1!r} q3={q3!r} "
                  f"spread={sp:.5f} less_farthest={tsp:.5f}")
            widest[name] = max(widest.get(name, 0.0), sp)
            trim.setdefault(name, []).append(tsp)
            medians.setdefault(name, []).append(med)
            every.setdefault(name, []).extend(vals)
    for name, sp in sorted(widest.items()):
        m = medians[name]
        print(f"widest {name}: {sp:.5f} -> 5x = {5 * sp:.5f}; "
              f"mean less-farthest {statistics.mean(trim[name]):.5f}; "
              f"all runs {spread(every[name]):.5f}; "
              f"second/first median {m[-1] / m[0]:.5f}")


if __name__ == "__main__":
    main(sys.argv[1:])
