"""Pallas TPU fused residual-add + RMSNorm for the decode hot path.

The eager decode trace spends 10 eqns per block boundary on
``add -> square -> reduce_sum -> broadcast -> div -> add -> rsqrt -> mul
-> broadcast -> mul``; this kernel is that window as ONE launch: row
blocks of (block_n, D) in VMEM, fp32 statistics, one HBM round trip for
both live outputs (the normed rows and the residual stream).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _res_rms_kernel(x_ref, r_ref, w_ref, o_ref, *s_ref, eps, has_residual):
    s = x_ref[...].astype(jnp.float32)
    if has_residual:
        # the model adds in the stream's dtype, then norms in f32: round
        # the sum to that dtype first (a no-op for an f32 stream)
        s = s + r_ref[...].astype(jnp.float32)
        s = s.astype(x_ref.dtype).astype(jnp.float32)
    var = jnp.mean(jnp.square(s), axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)[None]
    o_ref[...] = y.astype(o_ref.dtype)
    if s_ref:
        # the residual-stream output only exists when a residual was
        # actually added; the bare-norm form skips the dead (N, D) write
        s_ref[0][...] = s.astype(s_ref[0].dtype)


def residual_rmsnorm_kernel(
    x,
    weight,
    residual=None,
    *,
    eps=1e-5,
    block_n=256,
    interpret=False,
):
    """x: (N, D) -> [normed (N, D)] or [normed, pre-norm sum (N, D)].

    The pre-norm-sum output is emitted only when ``residual`` is given —
    without one the sum IS the input, so materializing it would be a
    dead full-width HBM write in the decode hot path.
    """
    n, d = x.shape
    has_res = residual is not None
    out_spec = pl.BlockSpec((block_n, d), lambda i: (i, 0))
    out_specs = [out_spec, out_spec] if has_res else [out_spec]
    sds = jax.ShapeDtypeStruct((n, d), x.dtype)
    out_shape = [sds, sds] if has_res else [sds]
    if has_res:
        r_spec = pl.BlockSpec((block_n, d), lambda i: (i, 0))
    else:
        residual = jnp.zeros((1, d), x.dtype)  # dummy, never read
        r_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    kernel = functools.partial(_res_rms_kernel, eps=eps, has_residual=has_res)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            r_spec,
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, residual, weight)
