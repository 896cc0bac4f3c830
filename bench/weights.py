"""Seeded weights of a dense decoder LM, made by the benchmark.

The benchmark makes the weights, not the program: the program is handed
them, and the plain reference reads the same arrays. The tree is the
program's parameter layout for its dense family (checked against the
program's own abstract init by ``check_layout``), with the usual scales:
embedding 0.02, projections ``fan_in ** -0.5``, norm scales 1.

Every leaf is drawn from its own key, ``fold_in(seed_key(seed), index)``,
so one leaf can be made again alone (the training check regenerates the
initial weights leaf by leaf instead of keeping a copy on the device).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: ``PRNGKey`` alone keeps only the
    low 32 bits, so the high bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def leaf_specs(c: dict) -> dict:
    """``{path: (shape, scale)}`` of every leaf; scale ``None`` means ones.
    ``c`` holds the configuration file's sizes."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, n = (c["num_attention_heads"], c["num_key_value_heads"],
                c["num_hidden_layers"])
    hd = c["head_dim"]
    return {
        ("embed", "table"): ((v, d), 0.02),
        ("layers", "ln1", "scale"): ((n, d), None),
        ("layers", "attn", "wq"): ((n, d, h, hd), d ** -0.5),
        ("layers", "attn", "wk"): ((n, d, kv, hd), d ** -0.5),
        ("layers", "attn", "wv"): ((n, d, kv, hd), d ** -0.5),
        ("layers", "attn", "wo"): ((n, h, hd, d), (h * hd) ** -0.5),
        ("layers", "ln2", "scale"): ((n, d), None),
        ("layers", "mlp", "w_up"): ((n, d, f), d ** -0.5),
        ("layers", "mlp", "w_down"): ((n, f, d), f ** -0.5),
        ("layers", "mlp", "w_gate"): ((n, d, f), d ** -0.5),
        ("final_norm", "scale"): ((d,), None),
        ("lm_head", "kernel"): ((d, v), d ** -0.5),
    }


def _leaf(key, index: int, shape, scale, dtype):
    if scale is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


@functools.partial(jax.jit, static_argnames=("c_items", "dtype"))
def _make(key, c_items, dtype):
    specs = leaf_specs(dict(c_items))
    return _nest({path: _leaf(key, i, shape, scale, dtype)
                  for i, (path, (shape, scale)) in enumerate(specs.items())})


def make_params(c: dict, seed: int, dtype) -> dict:
    """Every weight on the device in one jitted call, in ``dtype``."""
    items = tuple(sorted((k, c[k]) for k in (
        "hidden_size", "intermediate_size", "vocab_size",
        "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
        "head_dim")))
    return _make(seed_key(seed), items, jnp.dtype(dtype).name)


def make_leaf(c: dict, seed: int, path: tuple, dtype) -> jax.Array:
    """One leaf exactly as ``make_params`` makes it."""
    specs = leaf_specs(c)
    index = list(specs).index(path)
    shape, scale = specs[path]
    return jax.jit(_leaf, static_argnums=(1, 2, 3, 4))(
        seed_key(seed), index, shape, scale, jnp.dtype(dtype))


def leaf_paths(tree) -> list:
    """Key paths of a nested dict's leaves, as tuples of names."""
    return [tuple(k.key for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def check_layout(params, program_params) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes
    (``program_params`` is the program's abstract init)."""
    mine = {p: (x.shape, x.dtype) for p, x in zip(
        leaf_paths(params), jax.tree.leaves(params))}
    theirs = {p: (x.shape, x.dtype) for p, x in zip(
        leaf_paths(program_params), jax.tree.leaves(program_params))}
    if mine != theirs:
        raise ValueError(
            "benchmark weights do not match the program's parameter "
            f"layout: only here {sorted(set(mine.items()) - set(theirs.items()))}; "
            f"only in the program {sorted(set(theirs.items()) - set(mine.items()))}")
