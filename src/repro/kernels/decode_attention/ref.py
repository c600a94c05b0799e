"""Oracles for single-token decode attention over a KV cache.

``decode_attention_ref`` reads a contiguous per-sequence cache;
``paged_decode_attention_ref`` reads the same logical KV through a
block table over a pool of fixed-size token pages: position ``t`` of row
``b`` lives at ``pages[tables[b, t // bs], t % bs]``.  ``pool_rows``
writes such pages as one layer of the served pool's rows (``layout.py``),
the layout ``ops.paged_decode_attention`` reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.layout import row_shape

NEG_INF = -2.3819763e38


def decode_attention_ref(q, k, v, kv_len, *, scale: float):
    """q: (B,HQ,hd); k/v: (B,HKV,T,hd); kv_len: scalar — positions < kv_len
    are valid.  Returns (B,HQ,hd)."""
    b, hq, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32), kf) * scale
    mask = jnp.arange(t)[None, None, :] < kv_len
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,bhtd->bhd", p, vf)
    return o.astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, kv_lens, *,
                               scale: float):
    """q: (B,HQ,hd); k_pages/v_pages: (P,bs,HKV,hd) pooled token pages;
    block_tables: (B,NB) int32 page ids (entries past a row's length may be
    any value — they are masked); kv_lens: (B,) valid tokens per row.
    Returns (B,HQ,hd)."""
    b, hq, hd = q.shape
    n_pages, bs, hkv, _ = k_pages.shape
    nb = block_tables.shape[1]
    g = hq // hkv
    safe = jnp.clip(block_tables, 0, n_pages - 1)
    # gather each row's logical view: (B,NB,bs,HKV,hd) -> (B,HKV,T,hd)
    kg = k_pages[safe].reshape(b, nb * bs, hkv, hd).transpose(0, 2, 1, 3)
    vg = v_pages[safe].reshape(b, nb * bs, hkv, hd).transpose(0, 2, 1, 3)
    kf = jnp.repeat(kg, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(vg, g, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32), kf) * scale
    mask = jnp.arange(nb * bs)[None, None, :] < kv_lens[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,bhtd->bhd", p, vf)
    return o.astype(q.dtype)


def paged_decode_attention_quant_ref(q, k_pages, v_pages, k_scale, v_scale,
                                     block_tables, kv_lens, *, scale: float):
    """Quantized-pool oracle: k_pages/v_pages are (P,bs,HKV,hd) int8 with
    per-(token, head) f32 scales (P,bs,HKV); dequantize the whole pool in
    f32 and defer to ``paged_decode_attention_ref``."""
    kf = k_pages.astype(jnp.float32) * k_scale[..., None]
    vf = v_pages.astype(jnp.float32) * v_scale[..., None]
    return paged_decode_attention_ref(q, kf, vf, block_tables, kv_lens,
                                      scale=scale)


def pool_rows(pages, groups: int = 1):
    """(P, bs, HKV, w) token pages as one layer of pool rows,
    (1, groups, P, *row_shape): per head group and page, the group's heads
    head-major (head, token, value)."""
    n_pages, bs, hkv, w = pages.shape
    heads = hkv // groups
    rows = pages.reshape(n_pages, bs, groups, heads, w)
    rows = rows.transpose(2, 0, 3, 1, 4)                 # (G, P, heads, bs, w)
    return rows.reshape((1, groups, n_pages) + row_shape(bs, heads, w))
