"""Serving entry point: batched scan-prefill + greedy decode, served as streaming
requests through ``repro.serve.ServeScheduler``.

The demo form of the serving stack (docs/serving.md): each request is a
*generator* work function — each generated token is one yielded
``(tokens, logits)`` item (the greedy tokens and their logits, ``[B, 1]``
each), so the response's ``first_result_t`` is the time-to-first-token and
the subsystem's latency accounting applies unchanged to token serving. One loaded model answers any number of requests; each
request gets its own cache, which the programs update in place
(``cache_programs``).

Prefill is ``make_prefill_step`` — one jitted ``lax.scan`` dispatch over
the prompt positions instead of O(prompt_len) ``serve_step`` dispatches
(same teacher-forced single-token math; see the cache-position contract in
``repro.launch.steps``).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch relic_tiny --smoke \
      --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import build_model
from repro.runtime.metrics import span
from repro.serve import Request, ServeScheduler


def load(cfg):
    """The model in its serving layout (bf16 params), params from seed 0.
    Init is jitted so no f32 copy of a whole weight stack is ever live."""
    model = build_model(cfg.replace(param_dtype="bfloat16"))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def cache_programs(model, params, batch, cache_len):
    """The programs a request runs, around one cache buffer.

    A jitted program takes and returns the cache in its device's default
    layout. The decode step and the prefill hold the cache in that layout
    inside their layer loops too (``decode_step``'s ``cache_layout``), so
    both write their new entries into the donated buffer in place; left
    free, the compiler gives the loop a layout of its own and copies the
    whole cache into and out of it on every step. ``new_cache`` makes each
    request's cache on the device (for enc-dec models with the encoder's
    cross-attention entries filled in). ``params`` may be arrays or
    ``ShapeDtypeStruct``s with shardings; the cache lives where they do.
    Returns ``(new_cache(params, frames), prefill, step)``, jitted; the
    prefill and the step donate the cache."""
    cfg = model.cfg
    sharding = jax.tree.leaves(params)[0].sharding
    cache = jax.eval_shape(lambda: model.init_cache(batch, cache_len))
    formats = jax.jit(lambda c: c, in_shardings=sharding).lower(
        cache).compile().input_formats[0][0]
    held = dataclasses.replace(model, decode_step=functools.partial(
        model.decode_step,
        cache_layout=jax.tree.map(lambda f: f.layout, formats)))

    def init(params, frames):
        cache = model.init_cache(batch, cache_len)
        if cfg.family == "encdec":
            from repro.models.encdec import encode, prefill_cross_cache
            cache = prefill_cross_cache(cfg, params, cache,
                                        encode(cfg, params, frames))
        return cache

    return (jax.jit(init),
            jax.jit(make_prefill_step(held), donate_argnums=(1,)),
            jax.jit(make_serve_step(held), donate_argnums=(1,)))


def serve(model, params, prompts, *, gen, cache_len, lanes=1, frames=None):
    """Answer one request per ``[B, P]`` array in ``prompts`` on one loaded
    model. Returns the finished ``Response`` objects; each one's result is
    ``gen`` items of ``(tokens [B, 1], logits [B, 1])``, the greedy tokens
    and their logits: the prefill's prediction, then one per decode step.
    ``frames`` (enc-dec only) holds each request's encoder input."""
    batch, plen = prompts[0].shape
    frames = frames or [None] * len(prompts)
    new_cache, prefill, serve_step = cache_programs(model, params, batch,
                                                    cache_len)

    def generate(rid, prompt, req_frames):
        with span("serve.cache_init", rid=rid):
            cache = new_cache(params, req_frames)
        with span("serve.prefill", rid=rid):
            tok, logits, cache = prefill(params, cache, prompt)
        # The prefill prediction is token 0; it is delivered once it exists,
        # so first_result_t (TTFT) covers the prefill, not its dispatch.
        with span("serve.first_token", rid=rid):
            jax.block_until_ready(tok)
        yield tok, logits
        for t in range(plen, plen + gen - 1):
            with span("serve.step", rid=rid, pos=t):
                tok, logits, cache = serve_step(params, cache, tok,
                                                jnp.int32(t))
            yield tok, logits
        with span("serve.finish", rid=rid):
            jax.block_until_ready(tok)

    # Warm every program off the served path on one throwaway cache (the
    # prefill and the step donate it), so served requests measure
    # steady-state steps, not compilation.
    tok, _, cache = prefill(params, new_cache(params, frames[0]),
                            jnp.zeros_like(prompts[0]))
    jax.block_until_ready(serve_step(params, cache, tok, jnp.int32(plen))[0])
    del cache

    with ServeScheduler(lanes=lanes) as server:
        client = server.open_client("decode")
        resps = []
        for p, f in zip(prompts, frames):
            rid = Request.next_rid()
            resps.append(client.submit(generate, rid, p, f, rid=rid))
        for resp in resps:
            assert len(resp.result()) == gen, (len(resp.result()), gen)
    return resps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="relic_tiny")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=1,
                    help="RelicPool lanes backing the request server")
    args = ap.parse_args(argv)

    model, params = load(get_config(args.arch, smoke=args.smoke))
    cfg = model.cfg
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    frames = None
    if cfg.family == "encdec":
        frames = [jnp.asarray(
            rng.normal(size=(args.batch, cfg.frontend.n_tokens, cfg.d_model)),
            jnp.bfloat16)]

    (resp,) = serve(model, params, [prompts], gen=args.gen,
                    cache_len=args.prompt_len + args.gen, lanes=args.lanes,
                    frames=frames)
    gen_toks = jnp.concatenate([tok for tok, _ in resp.result()], axis=1)
    assert resp.first_result_t is not None and resp.complete_t is not None
    ttft = resp.first_result_t - resp.request.arrival_t
    dt = max(resp.complete_t - resp.first_result_t, 1e-9)
    tps = args.batch * (args.gen - 1) / dt
    print(f"generated {gen_toks.shape} tokens; {tps:.1f} tok/s "
          f"({dt / max(args.gen - 1, 1) * 1e3:.1f} ms/step, "
          f"ttft {ttft * 1e3:.1f} ms, lanes {args.lanes})")
    print("sample row:", np.asarray(gen_toks[0][:16]))
    return gen_toks


if __name__ == "__main__":
    from repro.runtime.config import enable_compile_cache

    enable_compile_cache()
    main()
