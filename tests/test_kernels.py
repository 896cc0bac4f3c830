"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Sweeps shapes (including non-aligned fallback paths) and dtypes per the
deliverable: every Pallas kernel is validated against ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _arr(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 384, 512, 128, 128, 256),
    (512, 256, 1024, 256, 256, 512),
    (100, 60, 36, 128, 128, 128),      # unaligned -> ref fallback path
])
def test_relic_matmul(rng, dtype, m, n, k, bm, bn, bk):
    x = _arr(rng, (m, k), dtype)
    y = _arr(rng, (k, n), dtype)
    out = ops.matmul(x, y, bm=bm, bn=bn, bk=bk)
    want = ref.matmul_ref(x, y)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 50)


@pytest.mark.parametrize("act", ["silu", "gelu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_relic_matmul_gated(rng, act, dtype):
    x = _arr(rng, (256, 256), dtype)
    wg = _arr(rng, (256, 128), dtype)
    wu = _arr(rng, (256, 128), dtype)
    out = ops.matmul_gated(x, wg, wu, act=act, bm=128, bn=128, bk=128)
    want = ref.matmul_gated_ref(x, wg, wu, act)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2.0 if dtype == jnp.bfloat16 else 2e-2)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,d,bq,bk", [
    (2, 128, 4, 4, 32, 64, 64),     # MHA
    (1, 256, 8, 2, 64, 128, 64),    # GQA 4:1
    (2, 128, 8, 1, 32, 64, 128),    # MQA
    (1, 96, 4, 2, 16, 64, 64),      # unaligned S -> fallback
])
def test_flash_attention(rng, causal, dtype, b, s, h, kv, d, bq, bk):
    q = _arr(rng, (b, s, h, d), dtype)
    k = _arr(rng, (b, s, kv, d), dtype)
    v = _arr(rng, (b, s, kv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal=causal).swapaxes(1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 2e-4,
        atol=2e-2 if dtype == jnp.bfloat16 else 2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,k,chunk", [
    (2, 64, 2, 16, 16),
    (1, 128, 4, 32, 32),
    (2, 96, 2, 16, 32),             # 96 % 32 == 0
])
def test_wkv6_kernel(rng, dtype, b, t, h, k, chunk):
    r = _arr(rng, (b, t, h, k), dtype)
    kk = _arr(rng, (b, t, h, k), dtype)
    v = _arr(rng, (b, t, h, k), dtype)
    lw = -jnp.exp(_arr(rng, (b, t, h, k), jnp.float32))  # aggressive decays
    u = _arr(rng, (h, k), jnp.float32)
    out = ops.wkv6(r, kk, v, lw, u, chunk=chunk)
    want = ref.wkv6_ref(*(a.swapaxes(1, 2) for a in (r, kk, v, lw)),
                        u).swapaxes(1, 2)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-3,
        atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (2, 64, 2, 16, 8, 16),
    (1, 128, 4, 32, 16, 32),
])
def test_ssd_kernel(rng, dtype, b, t, h, p, n, chunk):
    x = _arr(rng, (b, t, h, p), dtype)
    a = -jnp.abs(_arr(rng, (b, t, h), jnp.float32)) * 0.5
    bb = _arr(rng, (b, t, n), jnp.float32)
    cc = _arr(rng, (b, t, n), jnp.float32)
    out = ops.ssd(x, a, bb, cc, chunk=chunk)
    want = ref.ssd_ref(x.swapaxes(1, 2), a.swapaxes(1, 2), bb, cc).swapaxes(1, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-3,
        atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 3),
       st.sampled_from([16, 32]))
@settings(deadline=None, max_examples=10)
def test_flash_attention_property(b, heads_per_kv, kv, d):
    """Property: flash == reference for arbitrary GQA groupings."""
    rng = np.random.default_rng(b * 100 + heads_per_kv * 10 + kv)
    h = heads_per_kv * kv
    s = 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    want = ref.attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), causal=True).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_chunked_attention_matches_full(rng):
    """Model-level chunked (XLA flash) path == full attention."""
    from repro.models.attention import attention_chunked, attention_full

    q = _arr(rng, (2, 128, 4, 32), jnp.float32)
    k = _arr(rng, (2, 128, 2, 32), jnp.float32)
    v = _arr(rng, (2, 128, 2, 32), jnp.float32)
    for causal in (True, False):
        a = attention_chunked(q, k, v, causal=causal, chunk_q=32, chunk_k=64)
        b = attention_full(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_causal_skip_matches_full(rng):
    """Diagonal-band skipping is numerically exact for causal attention."""
    from repro.models.attention import attention_chunked, attention_full

    q = _arr(rng, (2, 192, 4, 16), jnp.float32)
    k = _arr(rng, (2, 192, 2, 16), jnp.float32)
    v = _arr(rng, (2, 192, 2, 16), jnp.float32)
    a = attention_chunked(q, k, v, causal=True, chunk_q=64, chunk_k=32,
                          causal_skip=True)
    b = attention_full(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    # unrolled variant (dry-run cost accounting path) is identical too
    c = attention_chunked(q, k, v, causal=True, chunk_q=64, chunk_k=32,
                          causal_skip=True, full_unroll=True)
    np.testing.assert_allclose(np.asarray(c), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_prefix_lm_mask(rng):
    from repro.models.attention import attention_chunked, attention_full

    q = _arr(rng, (1, 64, 4, 16), jnp.float32)
    k = _arr(rng, (1, 64, 4, 16), jnp.float32)
    v = _arr(rng, (1, 64, 4, 16), jnp.float32)
    a = attention_full(q, k, v, causal=True, prefix_len=16)
    b = attention_chunked(q, k, v, causal=True, chunk_q=16, chunk_k=16,
                          prefix_len=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    # prefix positions attend bidirectionally: row 0 must differ from causal
    c = attention_full(q, k, v, causal=True)
    assert not np.allclose(np.asarray(a)[0, 0], np.asarray(c)[0, 0])


def test_kernel_dispatch_refuses_unknown_backend(monkeypatch):
    """Interpret mode is for the CPU only: another non-TPU backend raises
    instead of silently running the kernel bodies in Python."""
    assert ops._interpret() is True          # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
