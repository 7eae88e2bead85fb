"""A test's own per-layer metric; nothing to read without a trace."""


def read(run):
    return None if run.trace is None else len(run.records)
