"""Pallas TPU decode attention (flash-decoding style).

One query token per sequence attends over a long KV cache.  Grid
(B, HQ, nKV) with the KV dim innermost; online-softmax accumulators live in
VMEM scratch.  This kernel is memory-bound by design — its job is streaming
the KV cache through VMEM at full HBM bandwidth; the q row is re-packed to
(8, hd) sublanes to keep the VPU busy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _dec_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale, block_kv, n_kv):
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
    s = jnp.where(kpos < kvlen_ref[0], s, NEG_INF)         # (1, bkv)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _paged_dec_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, scale, block_size, n_blocks):
    bb = pl.program_id(0)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bs, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # logical position of this page's tokens: page ikv of row bb holds
    # positions [ikv*bs, (ikv+1)*bs) — the block table only redirects
    # WHERE the page lives, not WHICH positions it holds
    kpos = ikv * block_size + \
        jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos < len_ref[bb], s, NEG_INF)          # (1, bs)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ikv == n_blocks - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _paged_dec_quant_kernel(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref,
                            vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                            scale, block_size, n_blocks):
    """Quantized-page variant of ``_paged_dec_kernel``: the pool holds int8
    payload pages + per-(token, head) f32 scale pages, and this kernel
    dequantizes each page tile AFTER the DMA — HBM traffic is the int8
    bytes + scales, never the widened bf16."""
    bb = pl.program_id(0)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    # dequantize in-register: int8 payload (bs, hd) x f32 scale (bs, 1)
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ikv * block_size + \
        jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos < len_ref[bb], s, NEG_INF)          # (1, bs)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ikv == n_blocks - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention_quant_kernel(q, k_pages, v_pages, k_scale,
                                        v_scale, block_tables, kv_lens, *,
                                        scale, interpret=False):
    """q: (B,HQ,1,hd); k_pages/v_pages: (HKV,P,bs,hd) int8; k_scale/v_scale:
    (HKV,P,bs,1) f32 per-(token, head) scales, DMA'd per page tile by the
    same scalar-prefetched block table that steers the payload fetch.  The
    trailing unit dim keeps the scale tile (bs, 1) legal under the TPU's
    (8, 128) tiling: its last dim equals the array's, and bs is a
    multiple of 8."""
    b, hq, _, hd = q.shape
    hkv, n_pages, bs = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    g = hq // hkv
    kernel = functools.partial(_paged_dec_quant_kernel, scale=scale,
                               block_size=bs, n_blocks=nb)
    page_spec = pl.BlockSpec((1, 1, bs, hd),
                             lambda bb, h, ikv, bt, kl: (h // g, bt[bb, ikv],
                                                         0, 0))
    scale_spec = pl.BlockSpec((1, 1, bs, 1),
                              lambda bb, h, ikv, bt, kl: (h // g,
                                                          bt[bb, ikv], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, kv_lens
        grid=(b, hq, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
            page_spec, scale_spec, page_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        interpret=interpret,
    )(block_tables, kv_lens, q, k_pages, k_scale, v_pages, v_scale)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_tables, kv_lens,
                                  *, scale, interpret=False):
    """q: (B,HQ,1,hd); k_pages/v_pages: (HKV,P,bs,hd) — note the head axis
    leads so each grid step DMAs one (bs,hd) page tile; block_tables:
    (B,NB) int32 page ids (scalar-prefetched: the index map reads them to
    steer each page fetch); kv_lens: (B,) int32 valid tokens per row."""
    b, hq, _, hd = q.shape
    hkv, n_pages, bs = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    g = hq // hkv
    kernel = functools.partial(_paged_dec_kernel, scale=scale,
                               block_size=bs, n_blocks=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, kv_lens
        grid=(b, hq, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bb, h, ikv, bt, kl: (h // g, bt[bb, ikv],
                                                     0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda bb, h, ikv, bt, kl: (h // g, bt[bb, ikv],
                                                     0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        interpret=interpret,
    )(block_tables, kv_lens, q, k_pages, v_pages)


def decode_attention_kernel(q, k, v, kv_len, *, scale, block_kv=512,
                            interpret=False):
    """q: (B,HQ,1,hd); k/v: (B,HKV,T,hd); kv_len: (1,) int32."""
    b, hq, _, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    nkv = t // block_kv
    kernel = functools.partial(_dec_kernel, scale=scale, block_kv=block_kv,
                               n_kv=nkv)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ikv: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, ikv: (bb, h // g, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, ikv: (bb, h // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ikv: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q, k, v)
