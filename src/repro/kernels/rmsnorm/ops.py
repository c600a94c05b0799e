"""Jitted wrapper for fused residual+RMSNorm."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm.kernel import rmsnorm_kernel


@functools.partial(jax.jit, static_argnames=("eps", "block_n", "interpret"))
def rmsnorm(x, weight, residual=None, *, eps=1e-5, block_n=256,
            interpret=False):
    """x: (..., D) -> (normed, residual_out) with leading dims flattened."""
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    r2 = residual.reshape(-1, d) if residual is not None else None
    n = x2.shape[0]
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    pad = (-n) % bn
    if pad:
        x2 = jnp.pad(x2, [(0, pad), (0, 0)])
        if r2 is not None:
            r2 = jnp.pad(r2, [(0, pad), (0, 0)])
    y, res = rmsnorm_kernel(x2, weight, r2, eps=eps, block_n=bn,
                            interpret=interpret)
    return y[:n].reshape(shape), res[:n].reshape(shape)
