"""Share of its roofline that the absorbed latent-attention core reaches:
the core's roofline seconds over the traced requests (each step the larger
of its latent-cache bytes over the peak bandwidth and its operations over
the bf16 peak, ``bench/flops_mla_moe.py``) over the device seconds of the
ops charged to the ``mla.core`` scope (``bench/scopes.py``), in percent."""

from bench import flops_mla_moe as flops
from bench import scopes
from bench.drivers.common import peaks


def read(facts, trace):
    if trace is None or not facts.get("traced_requests"):
        return None
    seconds = scopes.scope_seconds(trace, "mla.core")
    if not seconds:
        return None
    roofline = facts["traced_requests"] * flops.mla_core_roofline_s(
        facts["config"], facts["batch"], facts["prompt"], facts["gen"],
        peaks(facts["device_kind"]))
    return roofline / seconds * 100.0
