"""Jitted public wrapper: padding/alignment + layout around the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "softcap", "block_q", "block_kv",
    "interpret"))
def flash_attention(q, k, v, kv_len=None, *, scale: float, causal=True,
                    window=0, softcap=0.0, block_q=128, block_kv=128,
                    interpret=False):
    """q: (B,HQ,S,hd); k/v: (B,HKV,T,hd); kv_len: scalar int (None -> T)."""
    b, hq, s, hd = q.shape
    t = k.shape[2]
    if kv_len is None:
        kv_len = t
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    bq = min(block_q, max(8, 1 << (s - 1).bit_length()))
    bkv = min(block_kv, max(8, 1 << (t - 1).bit_length()))
    q_, pad_s = _pad_to(q, bq, 2)
    k_, pad_t = _pad_to(k, bkv, 2)
    v_, _ = _pad_to(v, bkv, 2)
    # pad head dim to the 128 lane width (zeros are exact: they add nothing
    # to q.k and produce zero output columns, sliced off below)
    q_, pad_h = _pad_to(q_, 128, 3)
    k_, _ = _pad_to(k_, 128, 3)
    v_, _ = _pad_to(v_, 128, 3)
    # padded queries sit at the causal tail: they attend to everything valid
    # but are discarded; padded KV masked via kv_len
    kv_len_eff = jnp.minimum(kv_len, t)

    o = flash_attention_kernel(
        q_, k_, v_, kv_len_eff, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_kv=bkv, q_offset=t - s,
        interpret=interpret)
    return o[:, :, :s, :hd]
