"""Jitted wrappers for the decode-attention kernels (contiguous + paged)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_kernel, paged_decode_attention_kernel,
    paged_decode_attention_quant_kernel)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv", "interpret"))
def decode_attention(q, k, v, kv_len=None, *, scale: float, block_kv=512,
                     interpret=False):
    """q: (B,HQ,hd); k/v: (B,HKV,T,hd); kv_len scalar (None -> T)."""
    b, hq, hd = q.shape
    t = k.shape[2]
    if kv_len is None:
        kv_len = t
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    bkv = min(block_kv, max(8, 1 << (t - 1).bit_length()))
    pad_t = (-t) % bkv
    if pad_t:
        widths = [(0, 0), (0, 0), (0, pad_t), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    pad_h = (-hd) % 128
    if pad_h:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_h)])
        k = jnp.pad(k, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, 0), (0, pad_h)])

    kv_len = jnp.minimum(kv_len, t)
    o = decode_attention_kernel(q[:, :, None, :], k, v, kv_len, scale=scale,
                                block_kv=bkv, interpret=interpret)
    return o[:, :, 0, :hd]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_lens, *,
                           scale: float, interpret=False,
                           k_scale=None, v_scale=None):
    """Decode attention through a block-table paged KV cache.

    q: (B,HQ,hd); k_pages/v_pages: (P,bs,HKV,hd) pooled token pages (the
    ``repro.kvcache`` layout); block_tables: (B,NB) int32 page ids (entries
    past a row's length may be any value); kv_lens: (B,) valid tokens.

    With ``k_scale``/``v_scale`` — (P,bs,HKV) f32, the quantized-pool
    layout — the pages are int8 payloads and the quantized kernel
    dequantizes each page tile after the DMA.

    The wrapper re-lays pages head-major — (HKV,P,bs,hd) — so each grid
    step of the kernel streams one (bs,hd) page tile picked by the
    scalar-prefetched block table; on a real TPU this transpose would be
    kept resident rather than re-done per step.
    """
    b, hq, hd = q.shape
    n_pages, bs, hkv, _ = k_pages.shape
    kp = jnp.transpose(k_pages, (2, 0, 1, 3))
    vp = jnp.transpose(v_pages, (2, 0, 1, 3))
    pad_h = (-hd) % 128
    if pad_h:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_h)])
        kp = jnp.pad(kp, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
        vp = jnp.pad(vp, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
    # out-of-range table entries (pool sentinels) must not steer a DMA
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, n_pages - 1)
    kv_lens = jnp.minimum(kv_lens.astype(jnp.int32),
                          block_tables.shape[1] * bs)
    if k_scale is not None:
        # (P,bs,HKV) -> (HKV,P,bs,1): head-major like the payload pages
        ks = jnp.transpose(k_scale, (2, 0, 1))[..., None].astype(jnp.float32)
        vs = jnp.transpose(v_scale, (2, 0, 1))[..., None].astype(jnp.float32)
        o = paged_decode_attention_quant_kernel(
            q[:, :, None, :], kp, vp, ks, vs, bt, kv_lens,
            scale=scale, interpret=interpret)
        return o[:, :, 0, :hd]
    o = paged_decode_attention_kernel(q[:, :, None, :], kp, vp, bt, kv_lens,
                                      scale=scale, interpret=interpret)
    return o[:, :, 0, :hd]
