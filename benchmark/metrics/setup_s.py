"""Process start to the first timed request: imports, cluster generation,
server start, warm-up and every compile."""


def read(run):
    return run.setup_s
