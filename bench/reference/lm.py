"""Plain reference of a dense decoder LM (Phi-3 family), in float32.

Written from the published description (arXiv:2404.14219; the Llama-style
block of Phi-3-mini): RMSNorm, rotary embeddings on query and key (the
rotate-half form), causal multi-head attention with a sliding window,
SwiGLU MLP, untied output head. It imports nothing of the program and
reads weights that the benchmark made (``bench/weights.py``).

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise rounds its operands to bfloat16. ``mode`` puts the
control in the reference's place: ``"int8"`` or ``"fp8"`` rounds both
operands of every product to that format (one scale per tensor, from its
largest magnitude) and accumulates in float32.

The forward pass runs layer by layer, so that only one layer's weights are
widened to float32 at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _quantize(x: jax.Array, mode: str) -> jax.Array:
    """Round-trip ``x`` through ``mode`` (``f32`` leaves it alone)."""
    if mode == "f32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if mode == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if mode == "fp8":
        s = amax / 448.0   # float8_e4m3fn's largest finite value
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision mode {mode!r}")


def mm(eq: str, a: jax.Array, b: jax.Array, mode: str = "f32") -> jax.Array:
    return jnp.einsum(eq, _quantize(a.astype(jnp.float32), mode),
                      _quantize(b.astype(jnp.float32), mode),
                      precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta):
    """x: [B, S, H, Dh], positions 0..S-1; rotate-half form."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """Causal attention; a query sees keys fewer than ``window`` positions
    back (``window`` None: all). k, v may have fewer heads (grouped)."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    qi = jnp.arange(q.shape[1])[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    keep = ki <= qi
    if window is not None:
        keep = keep & (qi - ki < window)
    s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", p, v, precision=HIGHEST)


def layer(c: dict, lp: dict, x: jax.Array, mode: str = "f32") -> jax.Array:
    """One block on the residual stream x [B, S, D] (float32)."""
    eps = c["rms_norm_eps"]
    h = rms_norm(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = rope(mm("bsd,dhk->bshk", h, a["wq"], mode), c["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, a["wk"], mode), c["rope_theta"])
    v = mm("bsd,dhk->bshk", h, a["wv"], mode)
    o = attention(q, k, v, c.get("sliding_window"))
    x = x + mm("bshk,hkd->bsd", o, a["wo"], mode)
    h = rms_norm(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"], mode))
    u = mm("bsd,df->bsf", h, m["w_up"], mode)
    return x + mm("bsf,fd->bsd", g * u, m["w_down"], mode)


def embed(params, tokens):
    return jnp.take(params["embed"]["table"].astype(jnp.float32), tokens, 0)


def head(c: dict, params, x, mode: str = "f32"):
    h = rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return mm("bsd,dv->bsv", h, params["lm_head"]["kernel"], mode)


def _layer_params(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _layer_jit(lp, x, c_items, mode):
    return layer(dict(c_items), lp, x, mode)


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _head_jit(params, x, c_items, mode):
    return head(dict(c_items), params, x, mode)


def _items(c: dict) -> tuple:
    return tuple(sorted((k, c.get(k)) for k in
                        ("rms_norm_eps", "rope_theta", "sliding_window")))


def logits(c: dict, params, tokens: jax.Array, mode: str = "f32"):
    """Logits [B, S, V] in float32 of tokens [B, S], layer by layer."""
    items = _items(c)
    x = jax.jit(embed)(params, tokens)
    for i in range(c["num_hidden_layers"]):
        x = _layer_jit(_layer_params(params, i), x, items, mode)
    return _head_jit({k: params[k] for k in ("final_norm", "lm_head")}, x,
                     items, mode)


# ------------------------------------------------------------------ training


def loss_fn(c: dict, params, batch, mode: str = "f32"):
    """Summed cross-entropy over the masked positions of a block of rows,
    and the number of those positions. Layers are recomputed in the
    backward pass (``jax.checkpoint``), so a block needs little memory."""
    x = embed(params, batch["tokens"])
    blk = jax.checkpoint(lambda lp, x: layer(c, lp, x, mode))

    def body(x, lp):
        return blk(lp, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    lg = head(c, params, x, mode)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return -(ll * mask).sum(), mask.sum()


def schedule(o: dict, step):
    """Linear warm-up to ``peak_lr`` (step 0 at ``peak_lr / warmup``), then
    cosine decay to ``min_lr_ratio * peak_lr`` at ``total_steps``."""
    step = jnp.float32(step)
    warm = (step + 1.0) / max(o["warmup_steps"], 1)
    t = jnp.clip((step - o["warmup_steps"])
                 / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * t))
    return o["peak_lr"] * jnp.where(step < o["warmup_steps"], warm, cos)


def adamw(o: dict, grads, mu, nu, params, step: int):
    """AdamW with decoupled weight decay and bias correction (Loshchilov
    and Hutter, 2019), after clipping the gradients to a global norm."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = schedule(o, step)
    b1, b2 = o["b1"], o["b2"]
    t = step + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + o["eps"])
                                  + o["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, grads
