"""Pallas TPU fused RMSNorm + projection matmul.

The decode trace norms each row then immediately contracts it with a
projection weight (qkv / MLP in / unembed).  Eager pays one launch per
eqn plus an HBM round trip for the normed intermediate; here the norm
runs on the VPU while the row block is already in VMEM for the MXU dot,
so the window is one launch and the intermediate never leaves VMEM.

Grid: (row blocks, F blocks).  The norm is recomputed per F block — VPU
work that is negligible next to the MXU dot and cheaper than a second
HBM pass.  fp32 statistics and accumulation throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_mm_kernel(x_ref, w_ref, p_ref, y_ref, n_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    scale = w_ref[...].astype(jnp.float32)[None]
    normed = (x * jax.lax.rsqrt(var + eps) * scale).astype(n_ref.dtype)
    y = jax.lax.dot_general(
        normed,
        p_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = y.astype(y_ref.dtype)
    n_ref[...] = normed


def rmsnorm_matmul_kernel(
    x,
    weight,
    w_proj,
    *,
    eps=1e-5,
    block_n=256,
    block_f=512,
    interpret=False,
):
    """x: (N, D), weight: (D,), w_proj: (D, F) -> ((N, F), normed (N, D))."""
    n, d = x.shape
    f = w_proj.shape[1]
    block_f = min(block_f, f)
    kernel = functools.partial(_rms_mm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n, f // block_f),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d,), lambda i, j: (0,)),
            pl.BlockSpec((d, block_f), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, block_f), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, f), w_proj.dtype),
            jax.ShapeDtypeStruct((n, d), x.dtype),
        ],
        interpret=interpret,
    )(x, weight, w_proj)
