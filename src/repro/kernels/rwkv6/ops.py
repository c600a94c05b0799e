"""Jitted wrapper for the chunked WKV6 kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import wkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, s0, *, chunk=16, interpret=False):
    """r,k,v,logw: (B,H,T,hd); u: (H,hd); s0: (B,H,hd,hd) -> (o, sT).

    Pads T to the chunk multiple with zero k/v and zero log-decay (w=1):
    padded steps add nothing to the state and their outputs are sliced off.
    """
    b, h, t, hd = r.shape
    pad = (-t) % chunk
    if pad:
        w4 = [(0, 0), (0, 0), (0, pad), (0, 0)]
        r = jnp.pad(r, w4)
        k = jnp.pad(k, w4)
        v = jnp.pad(v, w4)
        logw = jnp.pad(logw, w4)
    o, sT = wkv6_kernel(r, k, v, logw, u, s0, chunk=chunk,
                        interpret=interpret)
    return o[:, :, :t], sT
