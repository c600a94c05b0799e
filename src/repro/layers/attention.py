"""Attention: GQA, sliding-window, logit softcap, cross-attention, KV cache.

This is the pure-XLA path used for distribution lowering and smoke tests;
the Pallas flash/decode kernels in ``repro/kernels`` implement the same math
as the TPU-target hot-spot (see kernels/*/ref.py).  One of them is on the
served path: on the TPU a paged decode step reads the pool through
``paged_decode_attention`` instead of the XLA gather (``_decode_kernel``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.inference.kv_quant import dequantize_kv, quantize_kv
from repro.kernels import pallas_interpret
from repro.kernels.decode_attention.layout import row_shape
from repro.kernels.decode_attention.ops import (decode_kernel_fits,
                                                paged_decode_attention)
from repro.layers.common import apply_rope, dense_init, softcap, split_keys

NEG_INF = -2.3819763e38  # large negative, bf16-safe


def attention_init(key, cfg: ModelConfig, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), cfg.pdtype),
        "wk": dense_init(ks[1], (d, hkv * hd), cfg.pdtype),
        "wv": dense_init(ks[2], (d, hkv * hd), cfg.pdtype),
        "wo": dense_init(ks[3], (hq * hd, d), cfg.pdtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.pdtype)
    return p


def _project(params, x, cfg, name, heads):
    y = x @ params[f"w{name}"]
    if f"b{name}" in params:
        y = y + params[f"b{name}"].astype(y.dtype)
    b, s = x.shape[0], x.shape[1]
    return y.reshape(b, s, heads, cfg.hd)


def _expand_kv(k, g):
    """(B,T,HKV,hd) -> (B,T,HQ,hd).  The repeat keeps the head axis a single
    contiguous dim so GSPMD shards it cleanly on the model axis (a (HKV,G)
    split would not be expressible with one mesh axis)."""
    return jnp.repeat(k, g, axis=2) if g > 1 else k


def _mask(q_positions, kv_positions, causal, window, kv_valid):
    m = jnp.ones(q_positions.shape[:1] + (q_positions.shape[1],
                                          kv_positions.shape[1]), bool)
    if causal:
        m &= q_positions[:, :, None] >= kv_positions[:, None, :]
    if window:
        m &= (q_positions[:, :, None] - kv_positions[:, None, :]) < window
    if kv_valid is not None:
        m &= kv_valid[:, None, :]
    return m                                             # (B,S,T)


def mha(q, k, v, *, scale, causal, window, cap,
        q_positions, kv_positions, kv_valid=None):
    """Dense attention core (small sequences / decode).

    q: (B,S,HQ,hd)  k/v: (B,T,HKV,hd)
    q_positions: (B,S) | kv_positions: (B,T) | kv_valid: (B,T) bool or None
    """
    b, s, hq, hd = q.shape
    g = hq // k.shape[2]
    k, v = _expand_kv(k, g), _expand_kv(v, g)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    scores = softcap(scores, cap)
    mask = _mask(q_positions, kv_positions, causal, window, kv_valid)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
    return out


def flash_mha(q, k, v, *, scale, causal, window, cap,
              q_positions, kv_positions, kv_valid=None, block_kv: int = 512):
    """Flash-style attention: online softmax over KV blocks inside a scan —
    the (S,T) score matrix never materializes (this is the XLA analogue of
    the Pallas kernel in repro/kernels/flash_attention).

    Each block body is checkpointed so backward re-computes block scores
    instead of saving them.
    """
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    nk = -(-t // block_kv)
    pad = nk * block_kv - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)))
        valid = jnp.ones((b, t), bool) if kv_valid is None else kv_valid
        kv_valid = jnp.pad(valid, ((0, 0), (0, pad)))
    qf = q.astype(jnp.float32)

    kb = k.reshape(b, nk, block_kv, hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, block_kv, hkv, hd).transpose(1, 0, 2, 3, 4)
    pb = kv_positions.reshape(b, nk, block_kv).transpose(1, 0, 2)
    if kv_valid is not None:
        valb = kv_valid.reshape(b, nk, block_kv).transpose(1, 0, 2)
    else:
        valb = jnp.ones((nk, b, block_kv), bool)

    def body(carry, blk):
        m, lsum, acc = carry
        kj, vj, posj, valj = blk
        kj = _expand_kv(kj, g).astype(jnp.float32)
        vj = _expand_kv(vj, g).astype(jnp.float32)
        sc = jnp.einsum("bshd,bthd->bhst", qf, kj) * scale   # (B,H,S,Bk)
        sc = softcap(sc, cap)
        msk = _mask(q_positions, posj, causal, window, valj)
        sc = jnp.where(msk[:, None], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        lsum = lsum * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhst,bthd->bhsd", p, vj)
        return (m_new, lsum, acc), None

    m0 = jnp.full((b, hq, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, s), jnp.float32)
    a0 = jnp.zeros((b, hq, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (kb, vb, pb, valb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)     # (B,S,H,hd)


FLASH_MIN_SEQ = 1024


def attention_core(q, k, v, **kw):
    s, t = q.shape[1], k.shape[1]
    if s >= FLASH_MIN_SEQ and t >= FLASH_MIN_SEQ:
        return flash_mha(q, k, v, **kw)
    return mha(q, k, v, **kw)


def _touched_pages(block_tables, positions, bs: int, n_pages: int):
    """The page rows a call writes, and where each new token lands in them.

    A row's S new tokens sit at consecutive positions ``positions[b, 0]``
    onwards (as ``forward`` builds them), so they cover at most
    J = (S + 2·bs − 2) // bs consecutive logical blocks — one for a decode
    step, C/bs + 1 for a C-token chunk that may start mid-page.  Returns
    ``pages`` (B, J), the pool page of each covered block, and ``src``
    (B, J, bs), the index into the S new tokens of each token slot of that
    page (−1: the slot keeps what it holds).  A block past the row's table
    (a verify window crossing ``max_len``), a sentinel table entry, or a
    block no new token lands in gets page ``n_pages``, whose write drops.
    """
    s = positions.shape[1]
    nb = block_tables.shape[1]
    n_touch = (s + 2 * bs - 2) // bs
    blk = positions[:, :1] // bs + jnp.arange(n_touch, dtype=jnp.int32)
    src = (blk[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)
           - positions[:, :1, None])
    hit = (src >= 0) & (src < s)
    pages = jnp.take_along_axis(block_tables, jnp.minimum(blk, nb - 1),
                                axis=1)
    pages = jnp.where((blk < nb) & hit.any(-1), pages, n_pages)
    return pages, jnp.where(hit, src, -1)


def _write_rows(pool, layer, pages, src, new):
    """Whole-row read-modify-write of layer ``layer`` of one pool leaf.

    pool: (L, G, P, *row) — per layer, head group and page one row of
    bs·(H/G)·w values, head-major (head, token, value) whatever its
    trailing shape (``row_shape``); new: (B, S, H, w) values of the S new
    tokens (w = hd for K/V payloads, 1 for int8 scales).  The touched rows
    are gathered, the new tokens merged in with a select, and the rows
    scattered back whole; out-of-range pages (``_touched_pages``'
    sentinel) drop.  Per-element scatters would make XLA relayout the
    whole pool on the TPU.
    """
    n_groups, n_pages = pool.shape[1], pool.shape[2]
    b, _, h, w = new.shape
    n_touch, bs = src.shape[1], src.shape[2]
    g = jnp.arange(n_groups)[:, None, None]
    old = pool[layer, g, jnp.minimum(pages, n_pages - 1)[None]]
    old = old.reshape(n_groups, b, n_touch, h // n_groups, bs, w)
    tok = new[jnp.arange(b)[:, None], jnp.maximum(src, 0).reshape(b, -1)]
    tok = tok.reshape(b, n_touch, bs, n_groups, h // n_groups, w)
    tok = tok.transpose(3, 0, 1, 4, 2, 5).astype(pool.dtype)
    rows = jnp.where((src >= 0)[None, :, :, None, :, None], tok, old)
    return pool.at[layer, g, pages[None]].set(
        rows.reshape((n_groups, b, n_touch) + pool.shape[3:]), mode="drop")


def _read_rows(pool, layer, safe, h, w):
    """Every row's whole table of layer ``layer``, as (B, NB·bs, H, w)."""
    n_groups = pool.shape[1]
    b, nb = safe.shape
    g = jnp.arange(n_groups)[:, None, None]
    rows = pool[layer, g, safe[None]]                   # (G, B, NB, *row)
    rows = rows.reshape(n_groups, b, nb, h // n_groups, -1, w)
    return rows.transpose(1, 2, 4, 0, 3, 5).reshape(b, -1, h, w)


def _decode_kernel(pool, tables, n_kv, hd):
    """The Pallas read for a decode call over ``pool``, or None where the
    XLA read stays: off the TPU (there the XLA read is the reference, and
    tests patch this to run the kernel in the interpreter), and for a pool
    the kernel cannot read (``decode_kernel_fits``)."""
    if pallas_interpret() or not decode_kernel_fits(pool, tables, n_kv, hd):
        return None
    return paged_decode_attention


def _paged_attention_fwd(q, k, v, cache, layer, block_tables, positions,
                         lengths, cache_index, cfg: ModelConfig, *,
                         causal, window, scale):
    """Self-attention of layer ``layer`` over the block-table paged pool.

    ``cache`` holds the whole stack's pool (``make_paged_self_cache``
    stacked over layers): k_pages/v_pages (L, G, P, *row), one row of
    bs·(HKV/G)·hd values per (layer, head group, page), head-major within
    the row.  ``block_tables`` (B, NB) maps a row's logical token position
    ``t`` to page ``bt[b, t // bs]``.  The new K/V entries are written as
    whole page rows (``_write_rows``).  A decode call (one token per row)
    on a pool the kernel reads then runs the Pallas kernel
    ``paged_decode_attention`` over each row's live pages where they lie
    (``_decode_kernel``); every other call gathers each row's logical view
    back for the masked attention core.  Returns the attention output and
    the pool with layer ``layer`` updated.
    """
    assert block_tables is not None, "paged KV cache needs block_tables"
    b, s, hkv, hd = k.shape
    kp = cache["k_pages"]
    n_pages = kp.shape[2]
    bs_blk = math.prod(kp.shape[3:]) * kp.shape[1] // (hkv * hd)
    pages, src = _touched_pages(block_tables, positions, bs_blk, n_pages)
    if "k_scale" in cache:
        # quantize-on-write: only the int8 payload + per-(token,head) f32
        # scale ever live in the pool; the bf16 intermediate is transient
        (qk, sk), (qv, sv) = quantize_kv(k), quantize_kv(v)
        new = {"k_pages": qk, "v_pages": qv,
               "k_scale": sk[..., None], "v_scale": sv[..., None]}
    else:
        new = {"k_pages": k, "v_pages": v}
    new_cache = {name: _write_rows(cache[name], layer, pages, src, x)
                 for name, x in new.items()}
    kernel = None
    if s == 1 and causal and lengths is not None:
        kernel = _decode_kernel(kp, block_tables, hkv, hd)
    if kernel is not None:
        out = kernel(q[:, 0], new_cache["k_pages"], new_cache["v_pages"],
                     layer, block_tables, lengths, positions[:, 0],
                     scale=scale, kv_heads=hkv, window=window,
                     cap=cfg.attn_softcap)
        return out[:, None], new_cache
    safe = jnp.clip(block_tables, 0, n_pages - 1)
    kg, vg = (_read_rows(new_cache[n], layer, safe, hkv, hd)
              for n in ("k_pages", "v_pages"))
    if "k_scale" in cache:
        # dequantize-at-load: gather int8 pages + scales, widen to the
        # compute dtype only in the transient logical view
        kg = dequantize_kv(kg, _read_rows(new_cache["k_scale"], layer, safe,
                                          hkv, 1)[..., 0], k.dtype)
        vg = dequantize_kv(vg, _read_rows(new_cache["v_scale"], layer, safe,
                                          hkv, 1)[..., 0], v.dtype)
    t = kg.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if lengths is not None:
        # continuous-batching decode / speculative verify: row b just wrote
        # S tokens at lengths[b] .. lengths[b]+S-1 (the causal mask over
        # q_positions orders the in-window tokens)
        kv_valid = kv_pos < lengths[:, None] + s
    else:
        # (chunked) prefill: tokens [cache_index, cache_index + s) written
        kv_valid = kv_pos < cache_index + s
    out = attention_core(q, kg, vg, scale=scale, causal=causal,
                         window=window, cap=cfg.attn_softcap,
                         q_positions=positions, kv_positions=kv_pos,
                         kv_valid=kv_valid)
    return out, new_cache


def attention_fwd(
    params,
    x,
    cfg: ModelConfig,
    *,
    positions,                      # (B,S) int32 positions of x tokens
    causal: bool = True,
    window: int = 0,
    is_cross: bool = False,
    cross_kv: Optional[jax.Array] = None,   # (B,T,d) encoder/image states
    cache: Optional[dict] = None,           # {"k","v"}: (B,Tmax,HKV,hd)
    cache_index: Optional[jax.Array] = None,  # scalar int32 write offset
    lengths: Optional[jax.Array] = None,    # (B,) per-row lengths (cont. batching)
    shd=None,                               # sharding hook (head-parallel attn)
    block_tables: Optional[jax.Array] = None,  # (B,NB) page ids (paged cache)
    reduce=None,                            # TP output hook (psum in shard_map)
    layer=None,                             # paged: this layer's pool index
):
    """Returns (out (B,S,d), new_cache|None).

    Cross attention: if ``cross_kv`` is given, K/V are (re)computed from it
    (and written into ``cache`` when one is passed — prefill).  If
    ``cross_kv`` is None but ``is_cross``, K/V come from the cache (decode).

    ``reduce``: with wq/wk/wv column-sharded by head and wo row-sharded
    over a model axis (Megatron layout), the post-``wo`` output is a
    partial sum per device; ``reduce("attn_out", y)`` psums it inside a
    shard_map body.  None (single device / GSPMD) is identity.  ``cfg``
    must then carry the LOCAL head counts (the sharded backend passes a
    per-device config).
    """
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    scale = cfg.attn_scale or cfg.hd ** -0.5
    b, s = x.shape[0], x.shape[1]

    def finish(o):
        y = o.reshape(b, s, hq * cfg.hd) @ params["wo"]
        return reduce("attn_out", y) if reduce is not None else y

    q = _project(params, x, cfg, "q", hq)
    new_cache = None

    if is_cross:
        if cross_kv is not None:
            src = cross_kv.astype(x.dtype)
            k = _project(params, src, cfg, "k", hkv)
            v = _project(params, src, cfg, "v", hkv)
            if cache is not None:
                new_cache = {"k": k.astype(cache["k"].dtype),
                             "v": v.astype(cache["v"].dtype)}
        else:
            assert cache is not None, "cross-attn decode needs a cross cache"
            k, v = cache["k"], cache["v"]
            new_cache = cache
        t = k.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        out = attention_core(q, k, v, scale=scale, causal=False, window=0,
                             cap=cfg.attn_softcap, q_positions=positions,
                             kv_positions=kv_pos)
    else:
        k = _project(params, x, cfg, "k", hkv)
        v = _project(params, x, cfg, "v", hkv)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if cache is not None and "k_pages" in cache:
            out, new_cache = _paged_attention_fwd(
                q, k, v, cache, layer, block_tables, positions, lengths,
                cache_index, cfg, causal=causal, window=window, scale=scale)
            return finish(out), new_cache
        if shd is not None:
            if s == 1 and cache is not None:
                # decode: the q row is tiny — replicate it over tp and keep
                # the KV cache in place (T- or head-sharded per its spec).
                # Forcing head-sharded q here makes GSPMD all-gather the
                # ENTIRE cache per layer per token (~GBs/step).
                q = shd("q_decode", q)
            else:
                q = shd("q_heads", q)
                k = shd("kv_heads", k)
                v = shd("kv_heads", v)
        if cache is not None:
            if lengths is not None:
                # continuous-batching decode (S == 1) or speculative verify
                # (S == k+1): row b writes its S tokens at positions[b]
                # (lengths[b] + 0..S-1 by default) and sees only its own
                # prefix; the causal mask over q_positions orders the
                # in-window tokens.  Out-of-range positions (padding past
                # max_len) drop the write.
                rows = jnp.arange(b)[:, None]
                ck = cache["k"].at[rows, positions].set(k, mode="drop")
                cv = cache["v"].at[rows, positions].set(v, mode="drop")
                new_cache = {"k": ck, "v": cv}
                tmax = ck.shape[1]
                kv_pos = jnp.broadcast_to(jnp.arange(tmax, dtype=jnp.int32),
                                          (b, tmax))
                kv_valid = kv_pos <= positions[:, -1:]
                out = attention_core(q, ck, cv, scale=scale, causal=causal,
                                     window=window, cap=cfg.attn_softcap,
                                     q_positions=positions,
                                     kv_positions=kv_pos, kv_valid=kv_valid)
                return finish(out), new_cache
            # append k/v at cache_index, attend over the full cache
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, 1)
            new_cache = {"k": ck, "v": cv}
            tmax = ck.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(tmax, dtype=jnp.int32), (b, tmax))
            kv_valid = kv_pos < (cache_index + s)
            out = attention_core(q, ck, cv, scale=scale, causal=causal,
                                 window=window, cap=cfg.attn_softcap,
                                 q_positions=positions, kv_positions=kv_pos,
                                 kv_valid=kv_valid)
        else:
            out = attention_core(q, k, v, scale=scale, causal=causal,
                                 window=window, cap=cfg.attn_softcap,
                                 q_positions=positions, kv_positions=positions)

    if shd is not None and s == 1 and cache is not None and not is_cross:
        # keep the whole decode attention replicated-q / sharded-KV; only
        # the tiny (B,1,D) activation reshards before the wo matmul
        out = shd("q_decode", out)
    return finish(out), new_cache


def make_self_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def make_paged_self_cache(cfg: ModelConfig, num_pages: int, block_size: int,
                          dtype, quantized: bool = False, groups: int = 1):
    """Pool-global paged KV: pages are shared by all slots via block tables
    (``repro.kvcache``) rather than pre-carved per batch row.

    Each page is one row per head group: leaves are (groups, P, *row),
    bs·(HKV/groups)·hd values head-major (head, token, value), with no
    padding on the TPU.  K/V rows take ``row_shape``: where a head's tokens
    fill whole tiles the row is (rows, 128), so a page is one contiguous
    run of tiles that the decode kernel copies as it lies; otherwise it
    stays one flat lane-dense row.  ``groups`` > 1 splits the KV heads
    into that many whole-head groups, the axis tensor parallelism shards
    (``distributed.sharding.paged_cache_specs``).

    ``quantized``: flat int8 payload rows plus per-(token, head) f32 scale
    rows (G, P, bs·(HKV/groups)) (``inference.kv_quant`` numerics) — hd
    bytes + 4 scale bytes per (token, head) instead of 2*hd, so the same
    pool bytes hold ~2*hd/(hd+4) more tokens.
    """
    if cfg.n_kv_heads % groups:
        raise ValueError(f"groups={groups} must divide "
                         f"n_kv_heads={cfg.n_kv_heads}")
    heads = cfg.n_kv_heads // groups
    pages = (groups, num_pages)
    if quantized:
        flat = pages + (block_size * heads * cfg.hd,)
        scale = pages + (block_size * heads,)
        return {"k_pages": jnp.zeros(flat, jnp.int8),
                "v_pages": jnp.zeros(flat, jnp.int8),
                "k_scale": jnp.zeros(scale, jnp.float32),
                "v_scale": jnp.zeros(scale, jnp.float32)}
    shape = pages + row_shape(block_size, heads, cfg.hd)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def init_cross_cache(params, cfg: ModelConfig, cross_kv):
    """Precompute cross-attention K/V from encoder/image states."""
    k = _project(params, cross_kv, cfg, "k", cfg.n_kv_heads)
    v = _project(params, cross_kv, cfg, "v", cfg.n_kv_heads)
    return {"k": k, "v": v}
