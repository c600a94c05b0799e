"""Jitted wrapper for the fused RMSNorm + projection matmul kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused.rmsnorm_matmul.kernel import rmsnorm_matmul_kernel


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_n", "block_f", "interpret"),
)
def rmsnorm_matmul(
    x,
    weight,
    w_proj,
    *,
    eps=1e-5,
    block_n=256,
    block_f=512,
    interpret=False,
):
    """x: (..., D), w_proj: (D, F) -> (proj (..., F), normed (..., D))."""
    shape = x.shape
    d = shape[-1]
    f = w_proj.shape[1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    pad = (-n) % bn
    if pad:
        x2 = jnp.pad(x2, [(0, pad), (0, 0)])
    bf = min(block_f, f)
    pad_f = (-f) % bf
    w2 = jnp.pad(w_proj, [(0, 0), (0, pad_f)]) if pad_f else w_proj
    y, normed = rmsnorm_matmul_kernel(
        x2, weight, w2, eps=eps, block_n=bn, block_f=bf, interpret=interpret
    )
    return y[:n, :f].reshape(shape[:-1] + (f,)), normed[:n].reshape(shape)
