"""Mean client time of the Sync RPC per PreScore cycle: delta-encode, UDS,
the servicer's decode and resident apply."""

from benchmark.harness.stats import mean


def read(run):
    if run.rpc != "score_flat":
        return None
    return mean([r["sync_ms"] for r in run.records])
