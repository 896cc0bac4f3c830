"""Prefetch wait per training step: the mean of the traced window's
``data.wait`` spans (the train loop taking its next batch from the
prefetch pipeline's ring), in milliseconds."""

from bench.spans import span_record


def read(facts, trace):
    waits = span_record(trace, "data.wait")
    if not waits or not waits["count"]:
        return None
    return waits["seconds"] / waits["count"] * 1e3
