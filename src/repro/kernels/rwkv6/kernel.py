"""Pallas TPU chunked WKV6 (data-dependent-decay linear attention).

Grid (B, H, nC) with the chunk dim innermost: the (hd x hd) state carries
across chunks in VMEM scratch.  In-chunk cumulative decays are computed in
log space via a lower-triangular ones matmul (MXU-friendly cumsum); every
exp() argument is <= 0 so the kernel is overflow-safe for any decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sT_ref,
                 s_scr, *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    rc = r_ref[0, 0].astype(jnp.float32)                  # (C, hd)
    kc = k_ref[0, 0].astype(jnp.float32)
    vc = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)                 # (C, hd), <= 0
    u = u_ref[0].astype(jnp.float32)                      # (hd,)
    s = s_scr[...]

    c = rc.shape[0]
    tril_inc = jnp.tril(jnp.ones((c, c), jnp.float32))    # inclusive cumsum
    cum = jax.lax.dot_general(tril_inc, lw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, hd)
    cum_exc = cum - lw

    # A[t,s] = sum_i r[t,i] k[s,i] exp(cum_exc[t,i] - cum[s,i])  for s < t
    pair = cum_exc[:, None, :] - cum[None, :, :]          # (C, C, hd)
    strict = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)
    pair = jnp.where(strict[:, :, None], pair, NEG_INF)
    m = jnp.exp(pair)
    a = jnp.sum(rc[:, None, :] * kc[None, :, :] * m, axis=2)   # (C, C)
    diag = jnp.sum(rc * u[None, :] * kc, axis=1)          # (C,)
    a = a + diag[:, None] * jnp.eye(c, dtype=jnp.float32)

    inter = jax.lax.dot_general(a, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    dq = jnp.exp(cum_exc)                                 # (C, hd)
    cross = jax.lax.dot_general(rc * dq, s, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, 0] = (inter + cross).astype(o_ref.dtype)

    tot = cum[-1:, :]                                     # (1, hd)
    dk = jnp.exp(tot - cum)                               # (C, hd)
    s_new = jnp.exp(tot[0])[:, None] * s + jax.lax.dot_general(
        (kc * dk), vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ic == n_chunks - 1)
    def _done():
        sT_ref[0, 0] = s_new.astype(sT_ref.dtype)


def wkv6_kernel(r, k, v, logw, u, s0, *, chunk=16, interpret=False):
    """r,k,v,logw: (B,H,T,hd); u: (H,hd); s0: (B,H,hd,hd).
    T must be a multiple of chunk (ops.py pads).  Returns (o, sT)."""
    b, h, t, hd = r.shape
    nc = t // chunk
    kernel = functools.partial(_wkv6_kernel, chunk=chunk, n_chunks=nc)
    io_spec = pl.BlockSpec((1, 1, chunk, hd),
                           lambda bb, hh, ic: (bb, hh, ic, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            io_spec, io_spec, io_spec, io_spec,
            pl.BlockSpec((1, hd), lambda bb, hh, ic: (hh, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda bb, hh, ic: (bb, hh, 0, 0)),
        ],
        out_specs=[
            io_spec,
            pl.BlockSpec((1, 1, hd, hd), lambda bb, hh, ic: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u, s0)
