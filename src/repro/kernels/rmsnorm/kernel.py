"""Pallas TPU fused residual-add + RMSNorm.

The eager chain  add -> square -> mean -> rsqrt -> mul -> mul  is exactly
the kind of deterministic PS=1 chain the proximity miner recommends fusing
(launch tax: 6 kernels -> 1); this kernel is that fusion, hand-tiled:
row-blocks of (block_n, D) in VMEM, fp32 statistics, one HBM round trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_kernel(x_ref, w_ref, r_ref, o_ref, res_ref, *, eps, has_residual):
    x = x_ref[...].astype(jnp.float32)
    if has_residual:
        x = x + r_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)[None]
    o_ref[...] = y.astype(o_ref.dtype)
    res_ref[...] = x.astype(res_ref.dtype)


def rmsnorm_kernel(x, weight, residual=None, *, eps=1e-5, block_n=256,
                   interpret=False):
    """x: (N, D) -> (normed (N,D), new_residual (N,D))."""
    n, d = x.shape
    has_res = residual is not None
    if residual is None:
        residual = jnp.zeros((1, d), x.dtype)   # dummy, never read
    grid = (n // block_n,)
    kernel = functools.partial(_rms_kernel, eps=eps, has_residual=has_res)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)) if has_res
            else pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((n, d), x.dtype),
        ],
        interpret=interpret,
    )(x, weight, residual)
