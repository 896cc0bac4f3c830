"""train_step / serve_step builders — the jit roots the launcher, dry-run,
benchmarks, and examples all share."""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.registry import Model
from repro.optim import OptConfig, adamw_update, clip_by_global_norm, init_opt_state


def make_train_state(model: Model, key, oc: Optional[OptConfig] = None) -> dict:
    params = model.init(key)
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.zeros((), jnp.int32)}
    if oc is not None and oc.compress_grads:
        from repro.optim.compression import init_residual

        state["opt"]["residual"] = init_residual(params)
    return state


def abstract_train_state(model: Model) -> Any:
    """ShapeDtypeStruct pytree of the train state (no allocation)."""
    return jax.eval_shape(lambda: make_train_state(model, jax.random.PRNGKey(0)))


def make_train_step(model: Model, oc: OptConfig):
    def train_step(state: dict, batch: dict) -> Tuple[dict, dict]:
        def loss_fn(params, mb):
            return model.loss(params, mb)

        if oc.grad_accum > 1:
            # Microbatched gradient accumulation: scan over grad_accum slices
            # of the leading batch dim (activation memory / oc.grad_accum).
            def split(x):
                b = x.shape[0]
                assert b % oc.grad_accum == 0, (b, oc.grad_accum)
                return x.reshape(oc.grad_accum, b // oc.grad_accum,
                                 *x.shape[1:])

            mbs = jax.tree.map(split, batch)
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])

            def acc_body(carry, mb):
                g_acc, _ = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"], mb)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32) / oc.grad_accum,
                    g_acc, g)
                return (g_acc, metrics), None

            zero_m = {"loss": jnp.zeros(()), "ce": jnp.zeros(()),
                      "aux": jnp.zeros(()), "tokens": jnp.zeros(())}
            (grads, metrics), _ = jax.lax.scan(
                acc_body, (zero_g, zero_m), mbs)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"], batch)
        opt_state = dict(state["opt"])
        if oc.compress_grads:
            # int8 + error feedback: the quantized view is what a bandwidth-
            # starved pod axis would all-reduce; the residual carries the
            # quantization error to the next step (unbiased long-run).
            from repro.optim.compression import compress_with_feedback

            grads, residual = compress_with_feedback(
                grads, opt_state.pop("residual"))
        grads, gnorm = clip_by_global_norm(grads, oc.clip_norm)
        new_params, new_opt, lr = adamw_update(
            oc, grads, opt_state, state["params"], state["step"]
        )
        if oc.compress_grads:
            new_opt["residual"] = residual
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


def make_serve_step(model: Model):
    """One greedy decode step: (params, cache, tokens[B,1], pos) ->
    (next_tokens [B,1], logits [B,1,V], cache)."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return serve_step


def make_prefill_step(model: Model):
    """Teacher-forced prefill in ONE dispatch: ``lax.scan`` of
    ``decode_step`` over the prompt positions, carrying the cache.

    Cache-position contract: every model family's ``decode_step`` is
    strictly single-token — ``tokens`` is ``[B, 1]`` and ``pos`` is the
    absolute position of that token, which must advance by exactly 1 per
    call (attention reads ``kv_len = pos + 1``; SSM/hybrid states shift
    once per call). Prefill therefore cannot feed a multi-token chunk
    through ``decode_step``; what it *can* do is move the per-position
    loop from Python (O(prompt_len) jit dispatches) into a ``lax.scan``
    (one dispatch, identical per-position math). Pinned equivalent to the
    one-at-a-time loop by ``tests/test_serve.py``.

    Returns ``prefill(params, cache, prompts[B, P]) -> (next_tokens[B, 1],
    logits[B, 1, V], cache)`` — ``serve_step``'s triple, where
    ``next_tokens`` is the greedy prediction after the full prompt (exactly
    what the first decode step consumes) and ``logits`` are the last prompt
    position's.
    """

    def prefill(params, cache, prompts):
        toks = jnp.swapaxes(prompts, 0, 1)[:, :, None]        # [P, B, 1]
        positions = jnp.arange(prompts.shape[1], dtype=jnp.int32)

        def body(carry, xs):
            cache, _ = carry
            tok, pos = xs
            logits, cache = model.decode_step(params, cache, tok, pos)
            return (cache, logits), None

        logits0 = jnp.zeros((prompts.shape[0], 1, model.cfg.vocab_size),
                            jnp.float32)
        (cache, logits), _ = jax.lax.scan(body, (cache, logits0),
                                          (toks, positions))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return prefill


def abstract_serve_state(model: Model, shape: ShapeConfig):
    """(params_sds, cache_sds) for a decode shape (no allocation)."""
    cfg = model.cfg
    _, cache_len = model.input_specs(shape)
    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_len)
    )
    return params_sds, cache_sds
