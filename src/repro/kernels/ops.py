"""jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the compiled kernels run natively; on the
CPU backend `interpret=True` executes the kernel body in Python for
correctness validation; any other backend is an error, not a silent
interpreter fallback. Shapes that don't satisfy a kernel's
tiling constraints fall back to the jnp reference (production systems need
the fallback anyway — e.g. whisper's 1500-frame encoder).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.relic_matmul import relic_matmul as _relic_matmul
from repro.kernels.relic_matmul import relic_matmul_gated as _relic_matmul_gated
from repro.kernels.ssd import ssd_bhtp
from repro.kernels.wkv6 import wkv6_bhtk


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"Pallas kernels run natively on tpu or interpreted on cpu; "
            f"backend {backend!r} has neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(x, y, *, bm=256, bn=256, bk=512):
    m, k = x.shape
    n = y.shape[1]
    if m % min(bm, m) or n % min(bn, n) or k % min(bk, k):
        return ref.matmul_ref(x, y)
    return _relic_matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("act", "bm", "bn", "bk"))
def matmul_gated(x, w_gate, w_up, *, act="silu", bm=256, bn=256, bk=512):
    m, k = x.shape
    n = w_gate.shape[1]
    if m % min(bm, m) or n % min(bn, n) or k % min(bk, k):
        return ref.matmul_gated_ref(x, w_gate, w_up, act)
    return _relic_matmul_gated(x, w_gate, w_up, act=act, bm=bm, bn=bn, bk=bk,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, bq=256, bk=256):
    """Model layout [B,S,H,D] in/out; GQA via kv-head grouping."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    sq, sk = qt.shape[2], kt.shape[2]
    h, hkv = qt.shape[1], kt.shape[1]
    if sq % min(bq, sq) or sk % min(bk, sk) or h % hkv:
        o = ref.attention_ref(qt, kt, vt, causal=causal)
    else:
        o = flash_attention_bhsd(qt, kt, vt, causal=causal, bq=bq, bk=bk,
                                 interpret=_interpret())
    return o.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, logw, u, *, chunk=64):
    """Model layout [B,T,H,K] in/out; u [H,K]."""
    rt, kt, vt, wt = (a.swapaxes(1, 2) for a in (r, k, v, logw))
    t = rt.shape[2]
    if t % min(chunk, t):
        o = ref.wkv6_ref(rt, kt, vt, wt, u)
    else:
        o = wkv6_bhtk(rt, kt, vt, wt, u, chunk=chunk, interpret=_interpret())
    return o.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, a, b, c, *, chunk=128):
    """x [B,T,H,P]; a [B,T,H]; b/c [B,T,N] in model layout."""
    xt = x.swapaxes(1, 2)
    t = xt.shape[2]
    if t % min(chunk, t):
        o = ref.ssd_ref(xt, a.swapaxes(1, 2), b, c)
    else:
        o = ssd_bhtp(xt, a, b, c, chunk=chunk, interpret=_interpret())
    return o.swapaxes(1, 2)
