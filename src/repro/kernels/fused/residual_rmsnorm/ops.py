"""Jitted wrapper for the fused residual-add + RMSNorm kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused.residual_rmsnorm.kernel import residual_rmsnorm_kernel


@functools.partial(jax.jit, static_argnames=("eps", "block_n", "interpret"))
def residual_rmsnorm(
    x, weight, residual=None, *, eps=1e-5, block_n=256, interpret=False
):
    """x: (..., D) -> (normed, pre-norm sum), leading dims flattened.

    Without a residual the pre-norm sum is the input itself, so ``x`` is
    returned directly and the kernel emits only the normed output.
    """
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
    outs = residual_rmsnorm_kernel(
        x2, weight, r2, eps=eps, block_n=bn, interpret=interpret
    )
    y = outs[0][:n].reshape(shape)
    s = outs[1][:n].reshape(shape) if residual is not None else x
    return y, s
