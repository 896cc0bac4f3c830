"""Share of its roofline that the held experts reach: their roofline
seconds over the traced requests (each step the larger of the held
experts' weight bytes over the peak bandwidth and the operations of their
expected share of the picks over the bf16 peak, ``bench/flops_mla_moe.py``)
over the device seconds of the ops charged to the ``moe.experts`` scope
(``bench/scopes.py``: the sort, gathers, grouped products and combine), in
percent."""

from bench import flops_mla_moe as flops
from bench import scopes
from bench.drivers.common import peaks


def read(facts, trace):
    if trace is None or not facts.get("traced_requests"):
        return None
    seconds = scopes.scope_seconds(trace, "moe.experts")
    if not seconds:
        return None
    roofline = facts["traced_requests"] * flops.moe_experts_roofline_s(
        facts["config"], facts["batch"], facts["prompt"], facts["gen"],
        peaks(facts["device_kind"]))
    return roofline / seconds * 100.0
