"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("shape", [
    (2, 4, 2, 64, 64, 32), (1, 6, 2, 37, 37, 16), (2, 8, 8, 128, 256, 64),
    (1, 4, 1, 33, 65, 112),                       # kimi-style hd=112 padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(shape, dtype):
    B, HQ, HKV, S, T, hd = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, HQ, S, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, HKV, T, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, HKV, T, hd)).astype(dtype)
    o = flash_attention(q, k, v, interpret=True, scale=0.2, causal=True,
                        block_q=32, block_kv=32)
    r = attention_ref(q, k, v, scale=0.2, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window,cap", [(16, 0.0), (0, 8.0), (16, 8.0)])
def test_flash_attention_window_softcap(window, cap):
    B, HQ, HKV, S, T, hd = 1, 4, 2, 64, 64, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, HQ, S, hd))
    k = jax.random.normal(ks[1], (B, HKV, T, hd))
    v = jax.random.normal(ks[2], (B, HKV, T, hd))
    o = flash_attention(q, k, v, interpret=True, scale=0.2, causal=True, window=window,
                        softcap=cap, block_q=16, block_kv=16)
    r = attention_ref(q, k, v, scale=0.2, causal=True, window=window,
                      softcap=cap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(4, 40), hkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 3]), hd=st.sampled_from([8, 16]))
def test_flash_attention_property(s, hkv, g, hd):
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(ks[0], (1, hkv * g, s, hd))
    k = jax.random.normal(ks[1], (1, hkv, s, hd))
    v = jax.random.normal(ks[2], (1, hkv, s, hd))
    o = flash_attention(q, k, v, interpret=True, scale=hd ** -0.5, causal=True,
                        block_q=16, block_kv=16)
    r = attention_ref(q, k, v, scale=hd ** -0.5, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("shape", [(2, 4, 2, 128, 32), (1, 8, 8, 500, 64),
                                   (3, 6, 3, 96, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_shapes(shape, dtype):
    B, HQ, HKV, T, hd = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, HQ, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, HKV, T, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, HKV, T, hd)).astype(dtype)
    for kvlen in (T, T // 2, 5):
        o = decode_attention(q, k, v, kvlen, scale=0.2, block_kv=64,
                             interpret=True)
        r = decode_attention_ref(q, k, v, kvlen, scale=0.2)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=tol, rtol=tol)


# ------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("n,d", [(64, 96), (100, 256), (7, 64)])
@pytest.mark.parametrize("with_res", [False, True])
def test_rmsnorm(n, d, with_res):
    x = jax.random.normal(KEY, (n, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d,)) + 1.0
    res = jax.random.normal(jax.random.PRNGKey(2), (n, d)) if with_res else None
    y, r2 = rmsnorm(x, w, res, block_n=32, interpret=True)
    yr, rr = rmsnorm_ref(x, w, res)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r2), np.asarray(rr), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 50), d=st.sampled_from([32, 64, 128]))
def test_rmsnorm_property(n, d):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d))
    w = jnp.ones((d,))
    y, _ = rmsnorm(x, w, block_n=16, interpret=True)
    yr, _ = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ wkv6
@pytest.mark.parametrize("shape", [(1, 2, 32, 16), (2, 3, 45, 8),
                                   (1, 1, 16, 32)])
def test_wkv6(shape):
    B, H, T, hd = shape
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, H, T, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, H, T, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, H, T, hd))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, hd)) * 0.5 - 2)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    o, sT = wkv6(r, k, v, logw, u, s0, chunk=16, interpret=True)
    orf, srf = wkv6_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(srf),
                               atol=5e-4, rtol=1e-3)


def test_wkv6_extreme_decay():
    """Overflow-safety: very strong and very weak decays."""
    B, H, T, hd = 1, 1, 32, 8
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, H, T, hd))
    k = jax.random.normal(ks[1], (B, H, T, hd))
    v = jax.random.normal(ks[2], (B, H, T, hd))
    logw = jnp.where(jnp.arange(T)[None, None, :, None] % 2 == 0,
                     -50.0, -1e-4).astype(jnp.float32)
    logw = jnp.broadcast_to(logw, (B, H, T, hd))
    u = jnp.zeros((H, hd))
    s0 = jnp.zeros((B, H, hd, hd))
    o, sT = wkv6(r, k, v, logw, u, s0, chunk=8, interpret=True)
    orf, srf = wkv6_ref(r, k, v, logw, u, s0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               atol=1e-3, rtol=1e-3)


def test_wkv6_matches_model_chunked():
    """The Pallas kernel and the model's jnp chunked path agree."""
    from repro.layers.rwkv import wkv_chunked
    B, H, T, hd = 1, 2, 32, 8
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, hd))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, hd)) * 0.3 - 2)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    o_model, s_model = wkv_chunked(r, k, v, logw, u, s0, chunk=16)
    # kernel uses (B,H,T,hd) layout
    def tr(x):
        return x.transpose(0, 2, 1, 3)
    o_kern, s_kern = wkv6(tr(r), tr(k), tr(v), tr(logw), u, s0, chunk=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(tr(o_kern)), np.asarray(o_model),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_kern), np.asarray(s_model),
                               atol=5e-4, rtol=1e-3)
