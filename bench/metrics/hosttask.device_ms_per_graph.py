"""Device time per task graph: the device's busy time over the traced
window (the union of the GAP kernels' op intervals) divided by the graphs
run in it."""


def read(facts, trace):
    if trace is None or not facts.get("graphs_traced"):
        return None
    return trace["busy_s"] / facts["graphs_traced"] * 1e3
