"""Share of the chip's bf16 peak that the traced serve steps achieve while
the device is busy: the operations the traced requests' prefill and decode
positions require (``bench/flops.py``, causal attention over the keys each
position sees) over (device busy time x peak from ``bench/peaks.json``)."""

from bench.drivers.common import peaks


def read(facts, trace):
    if trace is None or not trace["busy_s"] or not facts.get("traced_flops"):
        return None
    peak = peaks(facts["device_kind"])["bf16_flops_per_s"]
    return facts["traced_flops"] / (trace["busy_s"] * peak) * 100.0
