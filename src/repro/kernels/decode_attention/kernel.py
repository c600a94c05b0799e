"""Pallas TPU decode attention (flash-decoding style).

One query token per sequence attends over a long KV cache; online-softmax
accumulators live in VMEM scratch.  These kernels are memory-bound by
design — their job is streaming the KV cache through VMEM at full HBM
bandwidth.

``paged_decode_attention_kernel`` reads the served pool where it lies:
the whole stacked leaf stays in HBM and each row's live pages are copied
into VMEM by async DMAs steered by the block table, several pages per
block, double-buffered across blocks and rows.  The contiguous kernel and
the int8 page kernel use a grid of (row, query head, KV block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.layout import LANES

NEG_INF = -2.3819763e38


def _dec_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, scale, block_kv, n_kv):
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
    s = jnp.where(kpos < kvlen_ref[0], s, NEG_INF)         # (1, bkv)

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


def _paged_dec_quant_kernel(bt_ref, len_ref, q_ref, k_ref, ks_ref, v_ref,
                            vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                            scale, block_size, n_blocks):
    """Paged decode over int8 ``(HKV, P, bs, hd)`` pages: one grid step per
    (row, query head, table entry), with the int8 payload and per-(token,
    head) f32 scale tiles fetched by the scalar-prefetched block table and
    dequantized AFTER the DMA — HBM traffic is the int8 bytes + scales,
    never the widened bf16."""
    bb = pl.program_id(0)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (1, hd)
    # dequantize in-register: int8 payload (bs, hd) x f32 scale (bs, 1)
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ikv * block_size + \
        jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    s = jnp.where(kpos < len_ref[bb], s, NEG_INF)          # (1, bs)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ikv == n_blocks - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention_quant_kernel(q, k_pages, v_pages, k_scale,
                                        v_scale, block_tables, kv_lens, *,
                                        scale, interpret=False):
    """q: (B,HQ,1,hd); k_pages/v_pages: (HKV,P,bs,hd) int8; k_scale/v_scale:
    (HKV,P,bs,1) f32 per-(token, head) scales, DMA'd per page tile by the
    same scalar-prefetched block table that steers the payload fetch.  The
    trailing unit dim keeps the scale tile (bs, 1) legal under the TPU's
    (8, 128) tiling: its last dim equals the array's, and bs is a
    multiple of 8."""
    b, hq, _, hd = q.shape
    hkv, n_pages, bs = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    g = hq // hkv
    kernel = functools.partial(_paged_dec_quant_kernel, scale=scale,
                               block_size=bs, n_blocks=nb)
    page_spec = pl.BlockSpec((1, 1, bs, hd),
                             lambda bb, h, ikv, bt, kl: (h // g, bt[bb, ikv],
                                                         0, 0))
    scale_spec = pl.BlockSpec((1, 1, bs, 1),
                              lambda bb, h, ikv, bt, kl: (h // g,
                                                          bt[bb, ikv], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, kv_lens
        grid=(b, hq, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
            page_spec, scale_spec, page_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda bb, h, ikv, bt, kl: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        interpret=interpret,
    )(block_tables, kv_lens, q, k_pages, k_scale, v_pages, v_scale)


def decode_attention_kernel(q, k, v, kv_len, *, scale, block_kv=512,
                            interpret=False):
    """q: (B,HQ,1,hd); k/v: (B,HKV,T,hd); kv_len: (1,) int32."""
    b, hq, _, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    nkv = t // block_kv
    kernel = functools.partial(_dec_kernel, scale=scale, block_kv=block_kv,
                               n_kv=nkv)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ikv: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, ikv: (bb, h // g, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bb, h, ikv: (bb, h // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda bb, h, ikv: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q, k, v)


def _paged_rows_kernel(layer_ref, lens_ref, pos_ref, bt_ref, q_ref, k_hbm,
                       v_hbm, o_ref, lse_ref, kbuf, vbuf, sems, m_scr,
                       l_scr, acc_scr, *, scale, cap, window, bs, nb,
                       n_pages, heads, rph, pack, g, qrows, ppb):
    """One head group's decode attention for one tile of rows, in one grid
    step: grid step (gi, ti) serves rows ``ti·tile`` to ``(ti+1)·tile``.

    A row reads its pages ``[first, endp)``: from the window's first token
    (or 0) to its last token, ``min(length, position)``; a row whose first
    table entry is no pool page reads none.  Blocks of ``ppb`` pages flow
    through two VMEM slots: the next block's DMAs (this row's, or the
    next row of the tile with pages) start before this block is waited
    for.  A page is fetched only where its table entry is a pool page; its
    tokens are masked otherwise.  Per KV head ``h`` the page holds ``rph``
    rows of 128 lanes, ``pack`` tokens per row; the query rows of ``h`` are its
    ``g`` query heads once per lane slot, so each query row keeps its own
    online softmax over the tokens of one slot, and the wrapper merges the
    slots by their log-sum-exp.
    """
    gi = pl.program_id(0)
    layer = layer_ref[0]
    n_rows = o_ref.shape[1]
    base = pl.program_id(1) * n_rows
    width = ppb * rph

    def span(b):
        """Row ``b`` of the tile: its first and end token, first and end
        page."""
        r = base + b
        end = jnp.minimum(lens_ref[r], pos_ref[r]) + 1
        start = jnp.maximum(pos_ref[r] - window + 1, 0) if window else 0
        head = bt_ref[r * nb]
        live = (head >= 0) & (head < n_pages)
        endp = jnp.where(live, jnp.minimum((end + bs - 1) // bs, nb), 0)
        return start, end, start // bs, endp

    def page(b, p, i, endp):
        idx = p + i
        pid = bt_ref[(base + b) * nb + jnp.minimum(idx, nb - 1)]
        ok = (idx < endp) & (pid >= 0) & (pid < n_pages)
        return jnp.where(ok, pid, 0), ok

    def copies(b, p, slot):
        endp = span(b)[3]
        for i in range(ppb):
            pid, ok = page(b, p, i, endp)
            yield ok, (
                pltpu.make_async_copy(k_hbm.at[layer, gi, pid],
                                      kbuf.at[slot, i], sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, gi, pid],
                                      vbuf.at[slot, i], sems.at[slot, 1]))

    def fetch(b, p, slot):
        for ok, pair in copies(b, p, slot):
            @pl.when(ok)
            def _():
                for c in pair:
                    c.start()

    def wait(b, p, slot):
        for ok, pair in copies(b, p, slot):
            @pl.when(ok)
            def _():
                for c in pair:
                    c.wait()

    def next_row(b):
        """The first row of the tile from ``b`` on that reads a page, and
        its first page; ``n_rows`` when none is left."""
        def idle(b):
            _, _, first, endp = span(jnp.minimum(b, n_rows - 1))
            return (b < n_rows) & (first >= endp)
        b = lax.while_loop(idle, lambda b: b + 1, b)
        return b, span(jnp.minimum(b, n_rows - 1))[2]

    # stale VMEM may hold NaNs; a masked token's weight is 0, never 0 * NaN
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    o_ref[...] = jnp.zeros_like(o_ref)
    lse_ref[...] = jnp.full_like(lse_ref, NEG_INF)

    b0, p0 = next_row(jnp.int32(0))

    @pl.when(b0 < n_rows)
    def _():
        fetch(b0, p0, 0)

    def block(state):
        b, p, slot = state
        start, end, first, endp = span(b)
        nxt_b, nxt_p = lax.cond(p + ppb < endp, lambda: (b, p + ppb),
                                lambda: next_row(b + 1))

        @pl.when(nxt_b < n_rows)
        def _():
            fetch(nxt_b, nxt_p, 1 - slot)

        wait(b, p, slot)

        @pl.when(p == first)
        def _():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # query row r serves query head r % g at lane slot r // g; column c
        # of the block holds tokens p*bs + c*pack + slot
        row = lax.broadcasted_iota(jnp.int32, (qrows, width), 0)
        col = lax.broadcasted_iota(jnp.int32, (qrows, width), 1)
        tok = p * bs + col * pack + row // g
        fetched = jnp.zeros((qrows, width), jnp.bool_)
        for i in range(ppb):
            fetched = fetched | ((col // rph == i) & page(b, p, i, endp)[1])
        live = fetched & (row < pack * g) & (tok >= start) & (tok < end)
        for h in range(heads):
            rs = slice(h * qrows, (h + 1) * qrows)
            hs = slice(h * rph, (h + 1) * rph)
            k = kbuf[slot, :, hs, :].reshape(width, LANES)
            v = vbuf[slot, :, hs, :].reshape(width, LANES)
            q = q_ref[0, b, rs, :].astype(k.dtype)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if cap:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(live, s, NEG_INF)
            m_prev, l_prev = m_scr[rs, :], l_scr[rs, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            w = jnp.where(live, jnp.exp(s - m_new[:, :1]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rs, :] = alpha * l_prev + jnp.sum(w, axis=1, keepdims=True)
            m_scr[rs, :] = m_new
            acc_scr[rs, :] = alpha[:, :1] * acc_scr[rs, :] + jnp.dot(
                w.astype(v.dtype), v, preferred_element_type=jnp.float32)

        @pl.when(nxt_b != b)
        def _():
            l = l_scr[...]
            o_ref[0, b] = acc_scr[...] / jnp.maximum(l[:, :1], 1e-30)
            lse_ref[0, b] = jnp.where(
                l > 0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)

        return nxt_b, nxt_p, 1 - slot

    lax.while_loop(lambda st: st[0] < n_rows, block, (b0, p0, jnp.int32(0)))


def paged_decode_attention_kernel(qx, k_pool, v_pool, layer, lens, pos,
                                  tables, *, scale, cap, window, block_size,
                                  heads, pack, g, ppb, tile,
                                  interpret=False):
    """Decode attention of layer ``layer`` over pool rows, ``tile`` rows per
    grid step, so the VMEM the kernel holds does not grow with B.

    qx: (G, B, heads·qrows, 128) f32 query rows (``ops.py`` builds them),
    B a multiple of ``tile``; k_pool/v_pool: the whole stacked pool leaf
    (L, G, P, heads·rph, 128), left in HBM; lens, pos: (B,) tokens held
    before this step and the query's position; tables: (B, NB) block
    table.  Returns the per query row normalized output and log-sum-exp,
    each (G, B, heads·qrows, 128) f32 (the log-sum-exp broadcast over
    lanes).
    """
    n_groups, b, n_q = qx.shape[:3]
    rows = k_pool.shape[3]
    kernel = functools.partial(
        _paged_rows_kernel, scale=scale, cap=cap, window=window,
        bs=block_size, nb=tables.shape[1], n_pages=k_pool.shape[2],
        heads=heads, rph=rows // heads, pack=pack, g=g, qrows=n_q // heads,
        ppb=ppb)
    spec = pl.BlockSpec((1, tile, n_q, LANES),
                        lambda gi, ti, *_: (gi, ti, 0, 0))
    out = jax.ShapeDtypeStruct(qx.shape, jnp.float32)
    buf = pltpu.VMEM((2, ppb, rows, LANES), k_pool.dtype)
    stats = pltpu.VMEM((n_q, LANES), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,              # layer, lens, pos, tables
            grid=(n_groups, b // tile),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[spec, spec],
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            stats, stats, stats]),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens, pos,
      tables.reshape(-1), qx, k_pool, v_pool)
