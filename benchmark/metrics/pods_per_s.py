"""Pods placed in the window over the window's seconds (scheduler_perf's
SchedulingThroughput); every cycle of every traffic places pods."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(r["placed"] for r in run.records) / run.window_s
