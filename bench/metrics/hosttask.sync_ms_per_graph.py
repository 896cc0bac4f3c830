"""Device sync time per task graph: the seconds of the traced window's
``graph.sync`` spans (each kernel's ``block_until_ready`` and the summary's
reads to the host, on every thread) over its ``graph.run`` spans, in
milliseconds."""

from bench.spans import span_record


def read(facts, trace):
    runs, part = span_record(trace, "graph.run"), span_record(trace,
                                                              "graph.sync")
    if not runs or not runs["count"] or part is None:
        return None
    return part["seconds"] / runs["count"] * 1e3
