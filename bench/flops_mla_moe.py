"""Operations and bytes of a DeepSeek-V3-style model served greedily, from
its shapes.

Every served position, of a prompt or decoded, is one step of ``batch``
sequences, one token each, through every layer in the absorbed form of
latent attention: a position at index ``p`` (0-based) attends to ``p + 1``
cached latents. Operations are multiply-adds times two.

- Per token, every matrix product of attention (``wq_a``, ``wq_nope``,
  ``wq_pe``, ``wkv_a``, ``wk_b`` (``W_UK``), ``wv_b`` (``W_UV``), ``wo``), of
  the dense layers' MLP, the router, the shared expert and the output
  head; the embedding lookup is no product.
- Held experts: a token picks ``num_experts_per_tok`` of the
  ``router_experts``; the ``n_routed_experts`` held here get the expected
  share of those picks, ``held / router_experts``.
- The absorbed core (``mla.core``): per layer and token, scores against
  the ``kv_lora_rank + qk_rope_head_dim`` values of each visible latent in
  each head, and values over the ``kv_lora_rank`` of each; it reads each
  visible latent's bytes once.
- The held experts (``moe.experts``): their weights once a step, and the
  operations of the expected picks.

A roofline time is the larger of bytes over the peak bandwidth and
operations over the peak rate, taken per step and summed.
"""

from __future__ import annotations

import numpy as np

BYTES = {"bfloat16": 2, "float32": 4}


def _attn_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    ql, kvl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
            + kvl * h * (nope + v) + h * v * d)


def _held_share(c: dict) -> float:
    return c["n_routed_experts"] / c["router_experts"]


def token_matmul_params(c: dict) -> float:
    """Weights a token multiplies by (held experts at their expected
    share of the picks)."""
    d, k = c["hidden_size"], c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - k
    f = c["moe_intermediate_size"]
    expert = 3 * d * f
    moe = (d * c["router_experts"] + c["n_shared_experts"] * expert
           + c["num_experts_per_tok"] * _held_share(c) * expert)
    return (c["num_hidden_layers"] * _attn_params(c)
            + k * 3 * d * c["intermediate_size"] + n * moe
            + d * c["vocab_size"])


def core_flops(c: dict, keys) -> np.ndarray:
    """Operations of one layer's absorbed core for one token over ``keys``
    visible latents."""
    kvl, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2.0 * c["num_attention_heads"] * np.asarray(keys, float) \
        * (2 * kvl + rope)


def core_bytes(c: dict, keys) -> np.ndarray:
    """Bytes of one layer's latents that one sequence's step reads."""
    return np.asarray(keys, float) * (c["kv_lora_rank"]
                                      + c["qk_rope_head_dim"]) \
        * BYTES[c["compute_dtype"]]


def _keys(plen: int, gen: int) -> np.ndarray:
    """Visible latents at each served position of a request."""
    return np.arange(1, plen + gen)


def serve_request_flops(c: dict, batch: int, prompt: int, gen: int) -> float:
    """One request: the prompt's positions, then ``gen - 1`` decode steps."""
    keys = _keys(prompt, gen)
    per_token = 2.0 * token_matmul_params(c) * len(keys) \
        + c["num_hidden_layers"] * core_flops(c, keys).sum()
    return float(batch * per_token)


def mla_core_roofline_s(c: dict, batch: int, prompt: int, gen: int,
                        peak: dict) -> float:
    """Roofline seconds of the absorbed core over one request."""
    keys = _keys(prompt, gen)
    t = np.maximum(batch * core_bytes(c, keys) / peak["hbm_bytes_per_s"],
                   batch * core_flops(c, keys) / peak["bf16_flops_per_s"])
    return float(c["num_hidden_layers"] * t.sum())


def moe_experts_roofline_s(c: dict, batch: int, prompt: int, gen: int,
                           peak: dict) -> float:
    """Roofline seconds of the held experts over one request."""
    expert = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    held = c["n_routed_experts"]
    step_bytes = held * expert * BYTES[c["param_dtype"]]
    step_flops = 2.0 * batch * c["num_experts_per_tok"] * _held_share(c) \
        * expert
    step = max(step_bytes / peak["hbm_bytes_per_s"],
               step_flops / peak["bf16_flops_per_s"])
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return float(n_moe * step * len(_keys(prompt, gen)))
