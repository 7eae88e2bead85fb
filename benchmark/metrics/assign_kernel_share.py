"""Share of the window's Assigns that the dense Pallas kernel served, in
percent: the cycle records whose reply ``path`` is "pallas".  A cell
whose Assigns fall back to the XLA scan (an input past the kernel's i32
bound, a node bucket past its VMEM limit) reads below 100."""


def read(run):
    if run.rpc != "assign" or not run.records:
        return None
    dense = sum(r.get("path") == "pallas" for r in run.records)
    return 100.0 * dense / len(run.records)
