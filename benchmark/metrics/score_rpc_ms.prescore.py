"""Mean client time of the ScoreFlat RPC: score_cycle or the incremental
rescore, top-k, the reply."""

from benchmark.harness.stats import mean


def read(run):
    if run.rpc != "score_flat":
        return None
    return mean([r["rpc_ms"] for r in run.records])
