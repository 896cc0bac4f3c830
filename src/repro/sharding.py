"""Partitioning rules: param-path patterns -> PartitionSpec, plus activation
sharding constraints.

Mesh axes:
  single-pod: ("data", "model")          = (16, 16)
  multi-pod:  ("pod", "data", "model")   = (2, 16, 16)

Layout (2D "FSDP + TP"):
  * `model` carries tensor/expert parallelism (Megatron column/row, vocab-
    parallel embeddings, expert sharding).
  * `data` carries the batch AND a ZeRO-3-style shard of every weight's
    non-model dimension.
  * `pod` carries batch only (pure DP between pods); gradients all-reduce
    over it. This is what the multi-pod dry-run proves out.

Activation constraints are applied through ``shard_act`` which is a no-op
unless a mesh context has been installed via ``use_sharding_rules`` — smoke
tests on 1 CPU device run the same model code without any mesh.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _axes() -> Optional[dict]:
    return getattr(_state, "axes", None)


@contextlib.contextmanager
def use_sharding_rules(mesh: Mesh):
    """Install mesh axes for activation constraints within the trace."""
    names = mesh.axis_names
    axes = {
        "batch": tuple(n for n in ("pod", "data") if n in names) or None,
        "model": "model" if "model" in names else None,
        "mesh": mesh,
    }
    prev = _axes()
    _state.axes = axes
    try:
        # NamedShardings carry their mesh explicitly, so no global mesh
        # context is required; constraints resolve against axes[...] here.
        yield
    finally:
        _state.axes = prev


def _resolve(token: Optional[str]):
    axes = _axes()
    if token is None or axes is None:
        return None
    if token == "batch":
        return axes["batch"]
    if token == "model":
        return axes["model"]
    raise ValueError(f"unknown logical axis {token!r}")


def _axis_prod(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    out = 1
    for n in names:
        out *= mesh.shape[n]
    return out


def fit_spec(mesh: Mesh, entries, shape) -> P:
    """Drop axis names whose size does not divide the dim (replicate instead).

    jit argument shardings must divide exactly; where a logical rule doesn't
    (e.g. 20 or 40 or 56 attention heads over model=16), we fall back to
    replication for that dim and the roofline records the cost. A tuple entry
    degrades to its longest prefix that divides.
    """
    fitted = []
    for d, entry in enumerate(entries):
        if entry is None or d >= len(shape):
            fitted.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        names = tuple(n for n in names if n in mesh.axis_names)
        while names and shape[d] % _axis_prod(mesh, names) != 0:
            names = names[:-1]
        if not names:
            fitted.append(None)
        elif len(names) == 1:
            fitted.append(names[0])
        else:
            fitted.append(tuple(names))
    return P(*fitted)


# --- experiment knobs (set by use_sharding_rules / hillclimb driver) -------
#
# activation layout for the RESIDUAL STREAM [B, S, D] (kind="resid"):
#   "tp"         — D sharded over model (baseline 2D layout)
#   "replicated" — residuals full per device (classic Megatron f/g)
#   "seq"        — S sharded over model (Megatron sequence parallelism)
_ACT_LAYOUTS = ("tp", "replicated", "seq", "mixed")


def set_activation_layout(mode: str) -> None:
    assert mode in _ACT_LAYOUTS, mode
    _state.act_layout = mode


def get_activation_layout() -> str:
    return getattr(_state, "act_layout", "tp")


def set_param_rule_overrides(rules) -> None:
    """Prepend (regex, logical-entries) rules; [] clears. Hillclimb only."""
    _state.rule_overrides = list(rules)


def _rule_overrides():
    return getattr(_state, "rule_overrides", [])


def current_mesh() -> Optional[Mesh]:
    axes = _axes()
    return axes["mesh"] if axes else None


def shard_act(x: jax.Array, *logical: Optional[str], kind: str = "act") -> jax.Array:
    """Constrain an activation, e.g. shard_act(h, 'batch', None, 'model').

    kind="resid" marks residual-stream constraints [B, S, D]; their layout is
    swappable via set_activation_layout for the §Perf experiments."""
    axes = _axes()
    if axes is None:
        return x
    mesh = axes["mesh"]
    tokens = list(logical)
    layout = get_activation_layout()
    if kind == "resid" and len(tokens) == 3:
        if layout == "replicated":
            tokens = [tokens[0], None, None]
        elif layout == "seq":
            tokens = [tokens[0], "model", None]
    elif kind == "blockin":
        # "mixed" layout: residuals stay model-sharded (memory), but block
        # inputs are replicated right AFTER the bf16 cast, so the per-block
        # all-gather moves bf16 — not the f32 the CPU dot upcast would force
        # (§Perf it7).
        if layout != "mixed":
            return x
        tokens = [tokens[0]] + [None] * (len(tokens) - 1)
    spec = fit_spec(mesh, [_resolve(t) for t in tokens], x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter partitioning rules
# ---------------------------------------------------------------------------

# Ordered (regex over '/'-joined path, spec builder) — first match wins.
# `spec` entries are logical: "model", "data", or None, matched to the
# *trailing* dims of the array (leading scan/stack dims get None).
_PARAM_RULES: list[tuple[str, tuple]] = [
    # embeddings / output heads: vocab-parallel, ZeRO on d_model
    (r"(^|/)embed/table$",        ("model", "data")),       # [V, D]
    (r"(^|/)lm_head/kernel$",     ("data", "model")),       # [D, V]
    # attention: Q and O column/row-parallel over heads; KV replicated on
    # model (GQA kv<TP) but ZeRO'd on data
    (r"(^|/)attn/wq$",            ("data", "model", None)),  # [D, H, Dh]
    (r"(^|/)attn/wk$",            ("data", None, None)),     # [D, Hkv, Dh]
    (r"(^|/)attn/wv$",            ("data", None, None)),
    (r"(^|/)attn/wo$",            ("model", None, "data")),  # [H, Dh, D]
    # dense MLP: column then row parallel
    (r"(^|/)mlp/w_(gate|up)$",    ("data", "model")),        # [D, F]
    (r"(^|/)mlp/w_down$",         ("model", "data")),        # [F, D]
    # MoE: experts over model, ZeRO over data on d_model dim
    (r"(^|/)moe/router$",         ("data", None)),           # [D, E]
    (r"(^|/)moe/w_(gate|up)$",    ("model", "data", None)),  # [E, D, F]
    (r"(^|/)moe/w_down$",         ("model", None, "data")),  # [E, F, D]
    # mamba2 / rwkv6 big projections
    (r"(^|/)ssm/w_in$",           ("data", "model")),        # [D, d_inner*...]
    (r"(^|/)ssm/w_out$",          ("model", "data")),        # [d_inner, D]
    (r"(^|/)rwkv/w_(r|k|v|g)$",   ("data", "model")),
    (r"(^|/)rwkv/w_o$",           ("model", "data")),
    # decode caches: batch over data; KV time axis over model (flash-decoding
    # style split-T — GSPMD inserts the partial-softmax collectives)
    (r"(^|/)cache/(k|v)$",        ("data", "model", None)),  # [B,T,Kv*Dh]
    (r"(^|/)cache/(xk|xv)$",      ("data", "model", None, None)),  # cross-attn
    (r"(^|/)layers/(k|v)$",       ("data", "model", None)),  # encdec cache
    (r"(^|/)layers/(xk|xv)$",     ("data", "model", None, None)),
    (r"(^|/)shared_attn/(k|v)$",  ("data", "model", None)),  # zamba2 cache
    (r"(^|/)cache/ssm_state$",    ("data", "model", None, None)),  # [B,H,P,N]
    (r"(^|/)cache/wkv_state$",    ("data", "model", None, None)),  # [B,H,Dh,Dh]
    (r"(^|/)cache/conv_state$",   ("data", None, "model")),        # [B,K-1,C]
    (r"(^|/)cache/shift_state$",  ("data", "model")),              # [B,D]
    # everything small (norms, biases, decay vectors, conv kernels): replicate
    (r".*",                       ()),
]


def param_entries(path: str, ndim: int):
    """Logical axis entries for one param ('/'-joined path + rank)."""
    for pat, logical in list(_rule_overrides()) + _PARAM_RULES:
        if re.search(pat, path):
            pad = ndim - len(logical)
            if pad < 0:
                # rule written for the unstacked rank; stacked arrays only
                # ever ADD leading dims, so negative pad means a rank mismatch
                # from e.g. fused dims — fall back to replication.
                return (None,) * ndim
            return (None,) * pad + tuple(logical)
    return (None,) * ndim


def param_spec(path: str, ndim: int) -> P:
    return P(*param_entries(path, ndim))


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(tree, mesh: Optional[Mesh] = None):
    """Map a param pytree (arrays or ShapeDtypeStructs) to PartitionSpecs.

    With a mesh, specs are divisibility-checked against each leaf's shape."""
    def one(kp, x):
        entries = param_entries(_path_str(kp), x.ndim)
        if mesh is None:
            return P(*entries)
        return fit_spec(mesh, entries, x.shape)

    return jax.tree_util.tree_map_with_path(one, tree)


def named_shardings(tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(tree, mesh))


def batch_spec(mesh: Mesh, ndim: int, batch_dim: int = 0) -> NamedSharding:
    names = mesh.axis_names
    batch_axes = tuple(n for n in ("pod", "data") if n in names) or None
    entries = [None] * ndim
    entries[batch_dim] = batch_axes
    return NamedSharding(mesh, P(*entries))
