"""Requests served per lane task: the window's requests (those that
finished inside it) over the ``serve.group`` spans that ended in the
traced window. A group is one batch, one cache and one decode loop for
all its members, so this is how many requests share each weight read."""

from bench.spans import span_record


def read(facts, trace):
    requests = facts.get("requests")
    groups = span_record(trace, "serve.group")
    if not requests or not groups or not groups["count"]:
        return None
    return len(requests) / groups["count"]
