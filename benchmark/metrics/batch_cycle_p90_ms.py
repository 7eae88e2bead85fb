"""90th percentile, over every Assign cycle of the window, of the client's
time from sending the cycle's Sync to receiving its Assign reply."""

from benchmark.harness.stats import percentile


def read(run):
    if run.rpc != "assign":
        return None
    return percentile([r["cycle_ms"] for r in run.records], 0.90)
