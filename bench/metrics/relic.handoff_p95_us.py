"""Relic hand-off, 95th percentile: over the traced window's submitted
tasks, the time from the end of the producer's ``task.submit`` span to the
start of the worker's ``task.run`` span with the same ``task`` id, in
microseconds, nearest rank. The task each wave's producer runs inline is
not handed off and gives no sample; a task that starts before its submit
returns gives a negative one."""

from bench.spans import of_run
from bench.stats import nearest_rank


def read(facts, trace):
    samples = (of_run(trace) or {}).get("handoff_s")
    if not samples:
        return None
    return nearest_rank(samples, 95) * 1e6
