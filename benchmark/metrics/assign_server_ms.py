"""Mean AssignReply.cycle_ms: the server's clock over launch, run_cycle
and the blocking readback."""

from benchmark.harness.stats import mean


def read(run):
    if run.rpc != "assign":
        return None
    return mean([r["server_ms"] for r in run.records])
