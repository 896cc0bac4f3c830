"""Producer join time per task graph: the seconds of the traced window's
``graph.join`` spans (the producer waiting on the handles of a wave's
submitted tasks) over its ``graph.run`` spans, in milliseconds."""

from bench.spans import span_record


def read(facts, trace):
    runs, part = span_record(trace, "graph.run"), span_record(trace,
                                                              "graph.join")
    if not runs or not runs["count"] or part is None:
        return None
    return part["seconds"] / runs["count"] * 1e3
