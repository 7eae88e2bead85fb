"""95th percentile, over every pod of the window, of the client's time from
sending its Sync to receiving its ScoreFlat reply."""

from benchmark.harness.stats import percentile


def read(run):
    if run.rpc != "score_flat":
        return None
    return percentile([r["cycle_ms"] for r in run.records], 0.95)
