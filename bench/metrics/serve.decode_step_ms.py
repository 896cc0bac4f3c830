"""Decode time per step: the median over the window's requests of
(complete - first token ready) / (generated tokens - 1), on the program's
own request timestamps."""

import statistics


def read(facts, trace):
    spans = facts.get("requests")
    if not spans or facts["gen"] < 2:
        return None
    return statistics.median((s["complete"] - s["first"]) / (facts["gen"] - 1)
                             for s in spans) * 1e3
