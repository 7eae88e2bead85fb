"""A test's own metric: every pod placed in the window."""


def read(run):
    return sum(r["placed"] for r in run.records)
