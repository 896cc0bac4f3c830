"""Uniform Model interface over every architecture family.

``build_model(cfg)`` returns a `Model` whose five callables are everything
the launcher, dry-run, tests, and benchmarks need:

  init(key) -> params
  loss(params, batch) -> (scalar, metrics)            # train step objective
  init_cache(batch, cache_len) -> cache               # decode state
  decode_step(params, cache, tokens, pos[, cache_layout]) -> (logits, cache)
  input_specs(shape) -> (batch_pytree of ShapeDtypeStruct, cache_len | None)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as ed
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss: Callable[[Any, dict], Tuple[jax.Array, dict]]
    init_cache: Callable[[int, int], Any]
    decode_step: Callable[[Any, Any, jax.Array, jax.Array], Tuple[jax.Array, Any]]
    input_specs: Callable[[ShapeConfig], Tuple[dict, Optional[int]]]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _lm_input_specs(cfg: ModelConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        batch = {"tokens": _sds((b, 1), "int32")}
        return batch, s
    specs = {
        "tokens": _sds((b, s), "int32"),
        "labels": _sds((b, s), "int32"),
        "mask": _sds((b, s), "float32"),
    }
    if cfg.family == "vlm":
        n_img = cfg.frontend.n_tokens
        specs["tokens"] = _sds((b, s - n_img), "int32")
        specs["labels"] = _sds((b, s - n_img), "int32")
        specs["mask"] = _sds((b, s - n_img), "float32")
        specs["patches"] = _sds((b, n_img, cfg.frontend.embed_dim),
                                cfg.compute_dtype)
    return specs, None


def _encdec_input_specs(cfg: ModelConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    t_enc = cfg.frontend.n_tokens
    if shape.kind == "decode":
        return {"tokens": _sds((b, 1), "int32")}, s
    return {
        "frames": _sds((b, t_enc, cfg.d_model), cfg.compute_dtype),
        "tokens": _sds((b, s), "int32"),
        "labels": _sds((b, s), "int32"),
        "mask": _sds((b, s), "float32"),
    }, None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        return Model(
            cfg=cfg,
            init=lambda key: ed.init_encdec(cfg, key),
            loss=lambda p, b: ed.encdec_loss(cfg, p, b),
            init_cache=lambda batch, cache_len: ed.init_encdec_cache(
                cfg, batch, cache_len),
            # The decoder scans its per-layer caches; no layout to hold.
            decode_step=lambda p, c, t, pos, cache_layout=None: (
                ed.encdec_decode_step(cfg, p, c, t, pos)),
            input_specs=lambda shape: _encdec_input_specs(cfg, shape),
        )
    return Model(
        cfg=cfg,
        init=lambda key: lm.init_lm(cfg, key),
        loss=lambda p, b: lm.lm_loss(cfg, p, b),
        init_cache=lambda batch, cache_len: lm.init_lm_cache(
            cfg, batch, cache_len),
        decode_step=lambda p, c, t, pos, cache_layout=None: lm.lm_decode_step(
            cfg, p, c, t, pos, cache_layout),
        input_specs=lambda shape: _lm_input_specs(cfg, shape),
    )
