"""Serving entry point: batched scan-prefill + greedy decode, served as streaming
requests through ``repro.serve.ServeScheduler``.

The demo form of the serving stack (docs/serving.md): each request is a
*generator* work function — each generated token is one yielded
``(tokens, logits)`` item (the greedy tokens and their logits, ``[B, 1]``
each), so the response's ``first_result_t`` is the time-to-first-token and
the subsystem's latency accounting applies unchanged to token serving. One loaded model answers any number of requests; queued
requests of equal shapes are served together as one batch, up to the
chip's ridge row count (``serve``), and each group gets its own cache,
which the programs update in place (``cache_programs``).

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
from repro.launch.peaks import ridge_rows
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


def plan_groups(keys, rows, max_rows, fits):
    """Split a queue into groups served as one batch, in queue order. A
    request joins the open group when its ``keys`` entry (its prompt's
    shape, and its frames') equals the group's, the group's ``rows`` stay
    within ``max_rows`` and ``fits(rows)`` holds for the merged rows;
    otherwise it opens the next group. ``max_rows=None`` merges nothing.
    Returns the groups as lists of queue indices."""
    groups = []
    for i, (key, r) in enumerate(zip(keys, rows)):
        if groups and max_rows is not None and keys[groups[-1][0]] == key:
            merged = r + sum(rows[j] for j in groups[-1])
            if merged <= max_rows and fits(merged):
                groups[-1].append(i)
                continue
        groups.append([i])
    return groups


def group_programs(model, params, n, prompt, frames, cache_len):
    """The programs one group of ``n`` requests runs, compiled ahead of
    time for its shapes: ``prompt`` and ``frames`` are one member's (arrays
    or ``ShapeDtypeStruct``s; ``frames`` None but for enc-dec). Returns
    ``(new_cache, prefill, step, join, split)``: ``cache_programs`` at the
    group's rows, lowered on the same abstract arguments as the benchmark's
    scope map; ``join`` concatenates the members' ``(prompt, frames)``
    along the batch and ``split`` cuts a step's ``(tokens, logits)`` back
    into the members' rows (both None for a group of one)."""
    sharding = jax.tree.leaves(params)[0].sharding

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows = n * prompt.shape[0]
    cache = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
        lambda: model.init_cache(rows, cache_len)))
    member = (sds(prompt.shape, prompt.dtype),
              None if frames is None else sds(frames.shape, frames.dtype))
    whole = jax.tree.map(lambda x: sds((rows, *x.shape[1:]), x.dtype),
                         member)
    new_cache, prefill, step = cache_programs(model, params, rows, cache_len)
    step = step.lower(params, cache, sds((rows, 1), jnp.int32),
                      sds((), jnp.int32)).compile()
    programs = (new_cache.lower(params, whole[1]).compile(),
                prefill.lower(params, cache, whole[0]).compile(), step)
    if n == 1:
        return (*programs, None, None)
    join = jax.jit(lambda *ms: jax.tree.map(
        lambda *xs: jnp.concatenate(xs), *ms)).lower(*[member] * n)
    split = jax.jit(lambda tok, logits: list(zip(
        jnp.split(tok, n), jnp.split(logits, n)))).lower(*step.out_info[:2])
    return (*programs, join.compile(), split.compile())


def _max_rows(params):
    """The chip's ridge row count for these weights (``peaks.ridge_rows``),
    None on a chip not in the table."""
    leaves = jax.tree.leaves(params)
    device = next(iter(leaves[0].sharding.device_set))
    bytes_per_weight = (sum(x.size * x.dtype.itemsize for x in leaves)
                        / sum(x.size for x in leaves))
    return ridge_rows(device.device_kind, bytes_per_weight)


def _cache_fits(model, params, cache_len, lanes):
    """``fits(rows)``: whether a cache of that many rows fits the device's
    free memory once for each lane that may hold one; always where the
    device reports no memory."""
    device = next(iter(jax.tree.leaves(params)[0].sharding.device_set))
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return lambda rows: True
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)

    def fits(rows):
        cache = jax.eval_shape(lambda: model.init_cache(rows, cache_len))
        return max(lanes, 1) * sum(x.size * x.dtype.itemsize for x in
                                   jax.tree.leaves(cache)) <= free

    return fits


def serve(model, params, prompts, *, gen, cache_len, lanes=1, frames=None,
          max_rows=None):
    """Answer one request per ``[B, P]`` array in ``prompts`` on one loaded
    model. Returns the finished ``Response`` objects, one per prompt, in
    order; each one's result is ``gen`` items of ``(tokens [B, 1], logits
    [B, 1])``, the greedy tokens and their logits: the prefill's
    prediction, then one per decode step. ``frames`` (enc-dec only) holds
    each request's encoder input.

    Queued requests of equal shapes are served as one batch
    (``plan_groups``): one cache, one prefill and one decode loop over the
    members' rows, up to ``max_rows`` rows, by default the chip's ridge
    row count (``peaks.ridge_rows``; nothing merges on a chip not in the
    table), and only where the merged cache fits the device's free
    memory. The programs of each group shape are compiled before the
    queue is read; ``prompts`` is read by ``len()`` and indexing until
    then, and iterated once, when the groups are submitted."""
    n = len(prompts)
    frames = [None] * n if frames is None else frames
    shapes = [(prompts[i].shape, prompts[i].dtype) for i in range(n)]
    frame_shapes = [None if f is None else (f.shape, f.dtype) for f in frames]
    if max_rows is None:
        max_rows = _max_rows(params)
    groups = plan_groups(list(zip(shapes, frame_shapes)),
                         [shape[0] for shape, _ in shapes], max_rows,
                         _cache_fits(model, params, cache_len, lanes))
    programs, plans = {}, []
    for g in groups:
        key = (len(g), shapes[g[0]], frame_shapes[g[0]])
        if key not in programs:
            programs[key] = group_programs(model, params, len(g),
                                           prompts[g[0]], frames[g[0]],
                                           cache_len)
        plans.append((g, programs[key]))

    def generate(rid, members, progs):
        new_cache, prefill, serve_step, join, split = progs
        prompt, req_frames = members[0] if join is None else join(*members)
        plen = prompt.shape[1]

        def parts(tok, logits):
            return [(tok, logits)] if split is None else split(tok, logits)

        with span("serve.cache_init", rid=rid):
            cache = new_cache(params, req_frames)
        with span("serve.prefill", rid=rid):
            tok, logits, cache = prefill(params, cache, prompt)
        # The prefill prediction is token 0; it is delivered once it exists,
        # so first_result_t (TTFT) covers the prefill, not its dispatch.
        with span("serve.first_token", rid=rid):
            jax.block_until_ready(tok)
        yield parts(tok, logits)
        for t in range(plen, plen + gen - 1):
            with span("serve.step", rid=rid, pos=t):
                tok, logits, cache = serve_step(params, cache, tok,
                                                jnp.int32(t))
            yield parts(tok, logits)
        with span("serve.finish", rid=rid):
            jax.block_until_ready(tok)

    with ServeScheduler(lanes=lanes) as server:
        client = server.open_client("decode")
        queue = iter(prompts)       # the one pass over the queue
        resps = []
        for g, progs in plans:
            members = [(next(queue), frames[i]) for i in g]
            rid = Request.next_rid()
            resps += client.submit_group(generate, len(g), rid, members,
                                         progs, rid=rid)
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
