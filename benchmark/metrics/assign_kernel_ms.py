"""Device time of the dense Pallas Assign kernel per Assign: the summed
durations of the trace's operations whose name matches the
configuration's ``kernel_pattern``, over the Assigns of the window."""


def read(run):
    t = run.trace
    if run.rpc != "assign" or t is None or not t["kernel_events"]:
        return None
    assigns = len(run.records)
    return t["kernel_s"] * 1e3 / assigns if assigns else None
