"""Idle device time at request boundaries: the idle seconds of the traced
window charged to ``serve.request`` and to its spans other than
``serve.step`` (cache allocation, prefill dispatch, first token, finish),
over the ``serve.request`` spans that ended in the window, in
milliseconds."""

from bench.spans import span_record

BOUNDARY = ("serve.request", "serve.cache_init", "serve.prefill",
            "serve.first_token", "serve.finish")


def read(facts, trace):
    requests = span_record(trace, "serve.request")
    if not requests or not requests["count"]:
        return None
    idle = sum(rec["idle_s"] for rec in (span_record(trace, name)
                                         for name in BOUNDARY) if rec)
    return idle / requests["count"] * 1e3
