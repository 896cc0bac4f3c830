"""Prefill time per prompt token: the median over the window's requests of
(first token ready - start on the lane) / prompt length, on the program's
own request timestamps. A request starts on its lane when admitted or when
the one before it on that lane finished, whichever is later, so the queue
in front of it is not counted."""

import statistics


def read(facts, trace):
    spans = facts.get("requests")
    if not spans:
        return None
    return statistics.median((s["first"] - s["start"]) / facts["prompt"]
                             for s in spans) * 1e3
