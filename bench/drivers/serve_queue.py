"""Serving with every request queued at the start of the window.

The program under test is ``repro.launch.serve.serve``: it warms its
prefill and decode programs, then submits the requests to its
``ServeScheduler`` (Relic lanes) and returns once every one has finished.
The window opens when the first request is submitted (the benchmark sees
that moment as serve() starting to iterate over the queue) and closes
``--seconds`` later; requests that finish after it are not counted, though
serve() waits for them.

Traffic keys: ``requests`` queued, each ``batch`` sequences of a
``prompt``-token prompt (ids uniform over the vocabulary, from the seed)
and ``gen`` greedy tokens, into a cache of ``cache_len``; ``lanes``;
``check_requests`` sampled for the check.

Check: of a sample of finished requests drawn from the seed, the plain
float32 reference runs once over each prompt with its served tokens; the
number compared is the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, weights
from bench.drivers.common import Context, Outcome, dense_model_config, device_now
from bench.reference import lm as ref_lm


class _Queue(list):
    """The requests, in order; ``on_start`` runs when serve() begins to
    submit them (its first iteration over the queue)."""

    def __init__(self, items, on_start):
        super().__init__(items)
        self._on_start = on_start

    def __iter__(self):
        self._on_start()
        return super().__iter__()


def served_gap(c: dict, params, prompt: np.ndarray, served: np.ndarray,
               mode: str = "f32", reference=None):
    """Widest gap (reference best - reference logit of the chosen token)
    over the served positions. The chosen token is the served one, or with
    ``mode`` other than f32 the one that the lower precision puts first.
    Returns (gap, reference logits at the served positions)."""
    plen = prompt.shape[1]
    toks = jnp.asarray(np.concatenate([prompt, served[:, :-1]], 1))
    if reference is None:
        reference = ref_lm.logits(c, params, toks)[:, plen - 1:]
    if mode == "f32":
        chosen = jnp.asarray(served)
    else:
        low = ref_lm.logits(c, params, toks, mode)[:, plen - 1:]
        chosen = jnp.argmax(low, -1)
    picked = jnp.take_along_axis(reference, chosen[..., None], -1)[..., 0]
    return float(jnp.max(jnp.max(reference, -1) - picked)), reference


def window_rate(arrivals, finished, seconds: float, tokens_per_request: int):
    """The window opens at the first arrival and lasts ``seconds``. Returns
    (opening time, tokens per second of the requests finished inside it
    over the time from the opening to the last of them, those requests in
    order of completion). Whole requests only."""
    t0 = min(arrivals)
    inside = sorted((r for r in finished if r.complete_t - t0 <= seconds),
                    key=lambda r: r.complete_t)
    if not inside:
        raise RuntimeError(f"no request finished within {seconds} s of "
                           "the first arrival; the window is too short")
    rate = len(inside) * tokens_per_request / (inside[-1].complete_t - t0)
    return t0, rate, inside


def lane_spans(finished, inside) -> list:
    """Start, first result and completion of each request in ``inside``, on
    the program's own timestamps. With every request queued at once on one
    lane, a request starts when it is admitted or when the one before it
    finished, whichever is later."""
    spans, prev = [], None
    for r in sorted(finished, key=lambda r: r.first_result_t):
        start = r.request.admit_t if prev is None else max(
            r.request.admit_t, prev.complete_t)
        if any(r is q for q in inside):
            spans.append({"start": start, "first": r.first_result_t,
                          "complete": r.complete_t})
        prev = r
    return spans


def run(ctx: Context) -> Outcome:
    from repro.launch import serve as serve_launch
    from repro.models import build_model

    c, t = ctx.config, ctx.traffic
    batch, plen, gen = t["batch"], t["prompt"], t["gen"]
    model = build_model(dense_model_config(
        c, param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"]))
    params = jax.block_until_ready(
        weights.make_params(c, ctx.seed, c["param_dtype"]))
    ctx.mark("weights")
    weights.check_layout(params, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    rng = ctx.rng(1)
    prompts_np = [rng.integers(0, c["vocab_size"], (batch, plen),
                               dtype=np.int32) for _ in range(t["requests"])]
    prompts = jax.block_until_ready([jnp.asarray(p) for p in prompts_np])
    ctx.mark("layout+prompts")

    # The caller's thread waits inside serve() while a lane serves the
    # queue: the span marks that wait in the trace. A span records only if
    # it is made while the trace runs.
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

    ok = [r for r in resps if r.status == "ok"]
    t0, rate, in_window = window_rate(
        [r.request.arrival_t for r in resps], ok, ctx.seconds,
        batch * (plen + gen))
    spans = lane_spans(ok, in_window)

    # The check: served tokens of a sample of the finished requests.
    index = {id(r): i for i, r in enumerate(resps)}
    pick = ctx.rng(2).choice(len(in_window), size=min(
        t["check_requests"], len(in_window)), replace=False)
    sample = []
    for k in sorted(pick):
        r = in_window[k]
        served = np.concatenate([np.asarray(tok) for tok, _ in r.result()], 1)
        sample.append((prompts_np[index[id(r)]], served))
    n_failed, n_ok = len(resps) - len(ok), len(ok)
    del resps, ok, in_window, prompts
    widest = max(served_gap(c, params, p, s)[0] for p, s in sample)

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
               "device_kind": ctx.devices[0].device_kind},
        checks=[("served_logit_gap", widest, ctx.limit("served_logit_gap"))],
        device=device, trace_path=trace_path)
