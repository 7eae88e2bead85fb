"""Mean client time of the Sync RPC per Assign cycle: delta-encode, UDS,
the servicer's decode and resident apply."""

from benchmark.harness.stats import mean


def read(run):
    if run.rpc != "assign":
        return None
    return mean([r["sync_ms"] for r in run.records])
