"""Serving one chip's share of an expert-parallel DeepSeek-V3 deployment,
with every request queued at the start of the window.

The program under test is ``repro.launch.serve.serve``, as in
``serve_queue``: the window opens when serve() starts to submit the queue
and closes ``--seconds`` later; whole requests that finished inside it are
counted. The model is the configuration's share (``held_first`` and
``n_routed_experts`` of the ``router_experts``), built from the
configuration file by ``bench.weights_mla_moe.model_config``.

Traffic keys: those of ``serve_queue`` (``requests``, ``batch``,
``prompt``, ``gen``, ``cache_len``, ``lanes``, ``check_requests``) and
``check_rows``: how many rows of each checked request, drawn from the
seed, the reference runs over.

Checks, over those rows (the plain float32 reference
``bench/reference/deepseek_v3.py``, once over each row's prompt and served
tokens):

- ``served_logit_gap``: the widest gap by which a served token's reference
  logit lies below the reference's best at that position;
- ``served_logit_err``: the widest |logit served with the token - the
  reference's logit of that token|;
- ``served_logit_err_p90``: the 90th percentile of that error over the
  checked positions.

The widest two catch a wrong token or logit anywhere; a router near-tie
that flips an expert between bf16 and float32 moves a few positions by
up to about a logit, so their limits lie above what sound runs read and
below what a wrong token reads. The percentile is the bulk of the
positions, which a lower precision than bf16 moves and near-ties do not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_mla_moe as flops
from bench import scopes, weights
from bench import weights_mla_moe as wm
from bench.drivers.common import Context, Outcome, device_now
from bench.drivers.serve_queue import _Queue, lane_spans, window_rate
from bench.reference import deepseek_v3 as ref


def build(c: dict):
    """The program's model of the configuration, built before any weight
    is made, so a program that lacks what the configuration needs fails
    first."""
    from repro.models import build_model

    return build_model(wm.model_config(c, param_dtype=c["param_dtype"],
                                       compute_dtype=c["compute_dtype"]))


CHECKS = ("served_logit_gap", "served_logit_err", "served_logit_err_p90")


def _numbers(best, picked, logits) -> dict:
    """The checks' numbers over the positions."""
    err = np.asarray(jnp.abs(logits - picked))
    return {"served_logit_gap": float(jnp.max(best - picked)),
            "served_logit_err": float(err.max()),
            "served_logit_err_p90": float(np.quantile(err, 0.9))}


def readings(c: dict, params, prompt: np.ndarray, served: np.ndarray,
             served_logits: np.ndarray, modes=()) -> dict:
    """The numbers of rows ``prompt`` [R, P] with their served tokens and
    logits [R, G] (``_numbers``), under ``"f32"``, and for each of
    ``modes`` the same with the reference in that precision put in the
    program's place (its own greedy tokens and their logits, under the
    served tokens' prefix). ``"shifted"`` in ``modes`` reads a fault:
    every served token one id on, with the served logits."""
    plen = prompt.shape[1]
    toks = jnp.asarray(np.concatenate([prompt, served[:, :-1]], 1))
    h32 = ref.hidden(c, params, toks)[:, plen - 1:]
    best, _, picked = ref.head(c, params, h32, jnp.asarray(served))
    out = {"f32": _numbers(best, picked, jnp.asarray(served_logits))}
    if "shifted" in modes:
        _, _, off = ref.head(c, params, h32, jnp.asarray(
            (served + 1) % c["vocab_size"]))
        out["shifted"] = _numbers(best, off, jnp.asarray(served_logits))
        modes = [m for m in modes if m != "shifted"]
    for mode in modes:
        low = ref.hidden(c, params, toks, mode)[:, plen - 1:]
        low_best, chosen, _ = ref.head(c, params, low, jnp.zeros_like(best,
                                                                      int),
                                       mode)
        del low
        best, _, picked = ref.head(c, params, h32, chosen)
        out[mode] = _numbers(best, picked, low_best)
    return out


def program_texts(model, params, batch: int, plen: int, cache_len: int):
    """The compiled text of the prefill and the decode step that serve()
    runs (compiled again from the same functions, so the persistent
    compile cache gives back the same programs)."""
    from repro.launch import serve as serve_launch

    sharding = jax.tree.leaves(params)[0].sharding

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cache = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
        lambda: model.init_cache(batch, cache_len)))
    _, prefill, step = serve_launch.cache_programs(model, params, batch,
                                                   cache_len)
    return [prefill.lower(params, cache, sds((batch, plen), jnp.int32))
            .compile().as_text(),
            step.lower(params, cache, sds((batch, 1), jnp.int32),
                       sds((), jnp.int32)).compile().as_text()]


def sample_rows(ctx_rng, batch: int, n: int) -> np.ndarray:
    return np.sort(ctx_rng.choice(batch, size=min(n, batch), replace=False))


def run(ctx: Context) -> Outcome:
    from repro.launch import serve as serve_launch

    c, t = ctx.config, ctx.traffic
    batch, plen, gen = t["batch"], t["prompt"], t["gen"]
    model = build(c)
    params = wm.make_params(c, ctx.seed, c["param_dtype"])
    ctx.mark("weights")
    weights.check_layout(params, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    rng = ctx.rng(1)
    prompts_np = [rng.integers(0, c["vocab_size"], (batch, plen),
                               dtype=np.int32) for _ in range(t["requests"])]
    prompts = jax.block_until_ready([jnp.asarray(p) for p in prompts_np])
    ctx.mark("layout+prompts")

    waiting = []

    def open_window():
        ctx.mark("serve warm-up")
        ctx.tracer.start()
        waiting.append(ctx.tracer.span("serve.wait"))
        waiting[0].__enter__()

    resps = serve_launch.serve(model, params, _Queue(prompts, open_window),
                               gen=gen, cache_len=t["cache_len"],
                               lanes=t["lanes"])
    waiting[0].__exit__(None, None, None)
    trace_path = ctx.tracer.stop()
    device = device_now(ctx.devices)
    if trace_path:
        scopes.write_map(trace_path, program_texts(model, params, batch, plen,
                                                   t["cache_len"]))

    ok = [r for r in resps if r.status == "ok"]
    t0, rate, in_window = window_rate(
        [r.request.arrival_t for r in resps], ok, ctx.seconds,
        batch * (plen + gen))
    spans = lane_spans(ok, in_window)

    # The check: sampled rows of a sample of the finished requests.
    index = {id(r): i for i, r in enumerate(resps)}
    pick = ctx.rng(2).choice(len(in_window), size=min(
        t["check_requests"], len(in_window)), replace=False)
    rows_rng = ctx.rng(3)
    sample = []
    for k in sorted(pick):
        r = in_window[k]
        rows = sample_rows(rows_rng, batch, t["check_rows"])
        served = np.concatenate([np.asarray(tok) for tok, _ in r.result()],
                                1)[rows]
        logits = np.concatenate([np.asarray(lg) for _, lg in r.result()],
                                1)[rows]
        sample.append((prompts_np[index[id(r)]][rows], served, logits))
    n_failed, n_ok = len(resps) - len(ok), len(ok)
    del resps, ok, in_window, prompts
    found = [readings(c, params, *s)["f32"] for s in sample]

    return Outcome(
        attempted=t["requests"],
        failed=n_failed,
        end_to_end={"setup_s": t0 - ctx.t_start,
                    "serve_tokens_per_s": rate},
        facts={"requests": spans, "prompt": plen, "gen": gen,
               # The trace runs from the first submission until serve()
               # returns, so it holds every finished request's steps.
               "traced_flops": n_ok * flops.serve_request_flops(
                   c, batch, plen, gen),
               "device_kind": ctx.devices[0].device_kind,
               "config": c, "batch": batch, "traced_requests": n_ok},
        checks=[(name, max(f[name] for f in found), ctx.limit(name))
                for name in CHECKS],
        device=device, trace_path=trace_path)
