"""Operations a dense decoder LM requires, computed from its shapes.

Counted as multiply-adds times two. A token's forward pass costs two
operations per weight of every matrix product (the embedding lookup is no
product), plus attention: ``q . k`` and ``p . v`` over the keys the token
may see, causal and windowed, at ``num_attention_heads * head_dim`` wide.
Training costs three forward passes (forward, and the two products of the
backward pass); recomputation under remat is not required, so it is not
counted.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * v


def visible_keys(c: dict, position: int) -> int:
    """Keys that the token at ``position`` (0-based) attends to."""
    window = c.get("sliding_window")
    return position + 1 if window is None else min(position + 1, window)


def forward_flops(c: dict, positions) -> float:
    """Forward operations of one sequence's tokens at ``positions``."""
    width = c["num_attention_heads"] * c["head_dim"]
    keys = sum(visible_keys(c, p) for p in positions)
    n = len(positions)
    return 2.0 * matmul_params(c) * n \
        + 4.0 * c["num_hidden_layers"] * width * keys


def serve_request_flops(c: dict, batch: int, prompt: int, gen: int) -> float:
    """One request: the prompt's positions, then ``gen - 1`` decode steps
    (the last generated token is never fed back)."""
    return batch * forward_flops(c, range(prompt + gen - 1))


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    return 3.0 * batch * forward_flops(c, range(seq))
