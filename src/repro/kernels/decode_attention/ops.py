"""Jitted wrappers for the decode-attention kernels (contiguous + paged)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_kernel, paged_decode_attention_kernel,
    paged_decode_attention_quant_kernel)
from repro.kernels.decode_attention.layout import LANES


@functools.partial(jax.jit, static_argnames=("scale", "block_kv", "interpret"))
def decode_attention(q, k, v, kv_len=None, *, scale: float, block_kv=512,
                     interpret=False):
    """q: (B,HQ,hd); k/v: (B,HKV,T,hd); kv_len scalar (None -> T)."""
    b, hq, hd = q.shape
    t = k.shape[2]
    if kv_len is None:
        kv_len = t
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    bkv = min(block_kv, max(8, 1 << (t - 1).bit_length()))
    pad_t = (-t) % bkv
    if pad_t:
        widths = [(0, 0), (0, 0), (0, pad_t), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    pad_h = (-hd) % 128
    if pad_h:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_h)])
        k = jnp.pad(k, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, 0), (0, pad_h)])

    kv_len = jnp.minimum(kv_len, t)
    o = decode_attention_kernel(q[:, :, None, :], k, v, kv_len, scale=scale,
                                block_kv=bkv, interpret=interpret)
    return o[:, :, 0, :hd]


# VMEM the paged kernel may fill with each of: the double-buffered query,
# output and log-sum-exp tiles of its rows, and the two slots of K and V
# page blocks.  Rows per grid step and pages per block are cut to fit, so
# whatever B, the kernel stays well inside the 16 MiB of VMEM a TPU v5e
# core gives a kernel by default.
ROWS_VMEM = PAGES_VMEM = 4 << 20
# SMEM for the scalar-prefetched block tables, lengths and positions: half
# of a TPU v5e core's 1 MiB (a 2048 x 128 table does not compile)
TABLES_SMEM = 1 << 19


def _page_bytes(pool) -> int:
    """VMEM of one page in the kernel's page buffers: K and V, two slots."""
    return 4 * pool.shape[3] * LANES * pool.dtype.itemsize


def decode_kernel_fits(pool, tables, n_kv: int, hd: int) -> bool:
    """Whether ``paged_decode_attention`` can read ``pool``, a stacked
    (L, G, P, *row) leaf of a model with ``n_kv`` KV heads of ``hd``,
    through (B, NB) block ``tables``: a bf16 pool in ``row_shape``'s tiled
    form, a head dim that packs whole tokens into 128 lanes, a page that
    fits the kernel's buffers, and tables that fit its scalar memory."""
    if pool.dtype != jnp.bfloat16 or pool.ndim != 5 or LANES % hd:
        return False
    heads = n_kv // pool.shape[1]
    b, nb = tables.shape
    return (pool.shape[3] % heads == 0 and pool.shape[4] == LANES
            and _page_bytes(pool) <= PAGES_VMEM
            and 4 * b * (nb + 2) <= TABLES_SMEM)


@functools.partial(jax.jit, static_argnames=(
    "scale", "kv_heads", "window", "cap", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           positions, *, scale: float, kv_heads: int,
                           window: int = 0, cap: float = 0.0,
                           interpret=False):
    """Decode attention of one query token per row over the paged pool.

    q: (B, HQ, hd); k_pool/v_pool: the whole stacked pool leaf (L, G, P,
    heads·rph, 128) (``row_shape``; G head groups of ``heads`` =
    kv_heads/G KV heads each), read in place; layer: this layer's index;
    block_tables: (B, NB) page ids (a row whose first entry is no pool
    page reads nothing, and a page id outside the pool steers no DMA);
    lengths: (B,) tokens each row held before this step's token; positions:
    (B,) the query's position.  Row b attends over tokens
    ``[max(0, pos - window + 1), min(length, pos) + 1)``, scores capped by
    ``cap·tanh(s/cap)`` when ``cap``.  Returns (B, HQ, hd) in q's dtype;
    a row that reads nothing returns zeros.

    Query head j·g + i (g = HQ/kv_heads) uses KV head j.  A pool row packs
    ``pack`` = 128/hd tokens per 128 lanes, so each query head is placed
    once per lane slot and each copy keeps its own softmax over that
    slot's tokens; the slots merge here by their log-sum-exp.
    """
    b, hq, hd = q.shape
    n_groups = k_pool.shape[1]
    heads = kv_heads // n_groups
    g = hq // kv_heads
    pack = LANES // hd
    block_size = k_pool.shape[3] // heads * LANES // hd
    qrows = -(-pack * g // 8) * 8
    nb = block_tables.shape[1]
    # rows per grid step: as many as fit ROWS_VMEM, in equal tiles; the
    # padding rows' tables hold no pool page, so they read nothing
    per_row = 3 * 2 * heads * qrows * LANES * 4
    n_tiles = -(-b // max(1, ROWS_VMEM // per_row))
    tile = -(-b // n_tiles)
    pad = n_tiles * tile - b
    # (G, B, heads, slot, g, slot', hd): query head (j, i) in lane slot s
    qr = q.astype(jnp.float32).reshape(b, n_groups, heads, g, hd)
    qr = qr.transpose(1, 0, 2, 3, 4)[:, :, :, None, :, None, :]
    eye = jnp.eye(pack, dtype=bool)[:, None, :, None]
    qx = jnp.where(eye, qr, 0.0).reshape(n_groups, b, heads, pack * g, LANES)
    qx = jnp.pad(qx, ((0, 0), (0, pad), (0, 0), (0, qrows - pack * g),
                      (0, 0)))
    lengths, positions = (jnp.pad(x.astype(jnp.int32), (0, pad))
                          for x in (lengths, positions))
    tables = jnp.pad(block_tables.astype(jnp.int32), ((0, pad), (0, 0)),
                     constant_values=-1)
    o, lse = paged_decode_attention_kernel(
        qx.reshape(n_groups, b + pad, heads * qrows, LANES), k_pool, v_pool,
        layer, lengths, positions, tables, scale=scale, cap=cap,
        window=window, block_size=block_size, heads=heads, pack=pack, g=g,
        ppb=max(1, min(16, nb, PAGES_VMEM // _page_bytes(k_pool))),
        tile=tile, interpret=interpret)
    # each query row's output sits in its own slot's lanes
    o = o[:, :b].reshape(n_groups, b, heads, qrows, LANES)[:, :, :, :pack * g]
    o = o.reshape(n_groups, b, heads, pack, g, pack, hd)
    o = jnp.stack([o[:, :, :, i, :, i] for i in range(pack)], axis=3)
    lse = lse[:, :b].reshape(n_groups, b, heads, qrows, LANES)
    lse = lse[..., :pack * g, 0]
    w = jax.nn.softmax(lse.reshape(n_groups, b, heads, pack, g), axis=3)
    out = jnp.sum(o * w[..., None], axis=3)             # (G, B, heads, g, hd)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, hq, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_quant(q, k_pages, v_pages, k_scale, v_scale,
                                 block_tables, kv_lens, *, scale: float,
                                 interpret=False):
    """Decode attention through int8 token pages.

    q: (B,HQ,hd); k_pages/v_pages: (P,bs,HKV,hd) int8 pooled token pages;
    k_scale/v_scale: (P,bs,HKV) f32 per-(token, head) scales;
    block_tables: (B,NB) int32 page ids (entries past a row's length may be
    any value); kv_lens: (B,) valid tokens.  The quantized kernel
    dequantizes each page tile after the DMA.

    The wrapper re-lays pages head-major — (HKV,P,bs,hd) — and pads hd to
    128 lanes, so each grid step of the kernel streams one (bs,hd) page
    tile picked by the scalar-prefetched block table.
    """
    b, hq, hd = q.shape
    n_pages, bs, hkv, _ = k_pages.shape
    kp = jnp.transpose(k_pages, (2, 0, 1, 3))
    vp = jnp.transpose(v_pages, (2, 0, 1, 3))
    pad_h = (-hd) % 128
    if pad_h:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad_h)])
        kp = jnp.pad(kp, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
        vp = jnp.pad(vp, [(0, 0), (0, 0), (0, 0), (0, pad_h)])
    # out-of-range table entries (pool sentinels) must not steer a DMA
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, n_pages - 1)
    kv_lens = jnp.minimum(kv_lens.astype(jnp.int32),
                          block_tables.shape[1] * bs)
    # (P,bs,HKV) -> (HKV,P,bs,1): head-major like the payload pages
    ks = jnp.transpose(k_scale, (2, 0, 1))[..., None].astype(jnp.float32)
    vs = jnp.transpose(v_scale, (2, 0, 1))[..., None].astype(jnp.float32)
    o = paged_decode_attention_quant_kernel(
        q[:, :, None, :], kp, vp, ks, vs, bt, kv_lens, scale=scale,
        interpret=interpret)
    return o[:, :, 0, :hd]
