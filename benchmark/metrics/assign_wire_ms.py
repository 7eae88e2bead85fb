"""Mean client Assign time less AssignReply.cycle_ms: transport and the
reply's encode and decode."""

from benchmark.harness.stats import mean


def read(run):
    if run.rpc != "assign":
        return None
    return mean([r["rpc_ms"] - r["server_ms"] for r in run.records])
