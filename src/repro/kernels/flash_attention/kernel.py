"""Pallas TPU flash attention: online-softmax tiles in VMEM, MXU matmuls.

Grid (B, HQ, nQ, nKV) — the KV dim innermost so the (m, l, acc) scratch
accumulators carry across KV tiles of one Q tile.  GQA is handled in the
K/V index_map (h -> h // group) so KV is never expanded in HBM.  Causal and
sliding-window masking use global position iota; tiles are f32 in VMEM,
matmuls hit the MXU at (block_q x hd) x (hd x block_kv).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _fa_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, scale, causal, window, softcap, block_q, block_kv,
               n_kv, q_offset):
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(2)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
    qpos = qpos + q_offset                 # right-aligned query positions
    kpos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    mask &= kpos < kvlen_ref[0]
    s = jnp.where(mask, s, NEG_INF)

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


def flash_attention_kernel(q, k, v, kv_len, *, scale, causal=True, window=0,
                           softcap=0.0, block_q=128, block_kv=128,
                           q_offset=0, interpret=False):
    """q: (B,HQ,S,hd) | k/v: (B,HKV,T,hd) | kv_len: (1,) int32 valid bound.

    S, T must be multiples of the block sizes and hd 128-aligned on real
    TPUs — ops.py pads.  q_offset: global position of q row 0 (right-aligned
    decode/prefill windows).  Returns (B,HQ,S,hd).
    """
    b, hq, s_len, hd = q.shape
    hkv, t_len = k.shape[1], k.shape[2]
    g = hq // hkv
    nq = s_len // block_q
    nkv = t_len // block_kv
    grid = (b, hq, nq, nkv)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv,
        n_kv=nkv, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bb, h, iq, ikv: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, iq, ikv: (bb, h // g, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, iq, ikv: (bb, h // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bb, h, iq, ikv: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q, k, v)
