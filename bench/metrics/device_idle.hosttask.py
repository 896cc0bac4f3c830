"""Share of the traced window in which the device ran no operation:
1 - (union of device-op intervals) / window, averaged over the chips."""


def read(facts, trace):
    if trace is None or trace["idle_share"] is None:
        return None
    return trace["idle_share"] * 100.0
