"""Paged KV-cache subsystem: allocator invariants, paged decode-attention
kernel vs oracles, paged forward vs contiguous forward (and the pool's
page rows against the contiguous cache's entries), engine-level token
equivalence (plain / chunked prefill / preempt-recompute / preempt-offload,
tp=2 against tp=1), the page ops on the pool's rows, the admit() overflow
guard, and LC-vs-CC offload pricing."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.device_model import PLATFORMS, offload_cost_s
from repro.inference.engine import Request, ServeEngine
from repro.inference.kv_quant import dequantize_kv
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref,
                                                pool_rows)
from repro.kvcache import (BlockPool, HostOffloadTier, PagedKVCache,
                           default_num_blocks)
from repro.models import forward, init_params, make_cache, make_paged_cache
from repro.telemetry.characterize import memory_pressure_sweep

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(KEY, cfg)
    return cfg, params


def _mk_requests(cfg, n=4, base_plen=7, max_new=5):
    rng = np.random.default_rng(0)
    return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size,
                                                base_plen + 3 * i)),
                    max_new_tokens=max_new)
            for i in range(n)]


def _tokens(done):
    return {r.rid: list(r.generated) for r in done}


# ------------------------------------------------------------ allocator
def test_block_pool_alloc_free_invariants():
    pool = BlockPool(8, 4)
    a = pool.alloc("a", 3)
    assert a == [0, 1, 2] and pool.used_blocks == 3
    b = pool.alloc("b", 2)
    assert b == [3, 4] and pool.free_blocks == 3
    assert pool.blocks_for(9) == 3 and pool.blocks_for(8) == 2
    freed = pool.free("a")
    assert freed == [0, 1, 2] and pool.free_blocks == 6
    # lowest ids first, including recycled ones
    c = pool.alloc("c", 4)
    assert c == [0, 1, 2, 5]
    assert pool.owned("c") == [0, 1, 2, 5]
    with pytest.raises(MemoryError):
        pool.alloc("d", 3)
    assert pool.ensure("c", 16) == []          # already covered
    assert pool.utilization == pytest.approx(6 / 8)


def test_block_pool_table_row_and_validation():
    with pytest.raises(ValueError):
        BlockPool(0, 4)
    with pytest.raises(ValueError):
        BlockPool(4, 0)
    pool = BlockPool(4, 2)
    pool.alloc("x", 2)
    row = pool.table_row("x", 4, sentinel=99)
    assert list(row) == [0, 1, 99, 99]
    assert list(pool.table_row("ghost", 3, sentinel=7)) == [7, 7, 7]


def test_default_num_blocks():
    assert default_num_blocks(4, 64, 16) == 16    # 4 slots x 4 blocks
    assert default_num_blocks(4, 64, 16, num_blocks=5) == 5
    with pytest.raises(ValueError):
        default_num_blocks(4, 64, 16, num_blocks=0)


# ------------------------------------------------------------ kernel
def _scatter_pages(k, v, lens, bs, n_pages, seed=0):
    """Contiguous (B,HKV,T,hd) -> permuted pages + tables (np)."""
    b, hkv, t, hd = k.shape
    nb = t // bs
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_pages)
    tables = np.full((b, nb), n_pages + 3, np.int32)     # sentinel pad
    kp = np.zeros((n_pages, bs, hkv, hd), np.float32)
    vp = np.zeros((n_pages, bs, hkv, hd), np.float32)
    kn, vn = np.asarray(k), np.asarray(v)
    nxt = 0
    for row in range(b):
        for i in range(-(-int(lens[row]) // bs)):
            pg = int(perm[nxt])
            nxt += 1
            tables[row, i] = pg
            kp[pg] = kn[row, :, i * bs:(i + 1) * bs].transpose(1, 0, 2)
            vp[pg] = vn[row, :, i * bs:(i + 1) * bs].transpose(1, 0, 2)
    return jnp.asarray(kp), jnp.asarray(vp), tables


def _pool_kernel(q, kp, vp, tables, lens):
    """The pool-row decode kernel over (P,bs,HKV,hd) pages written as one
    layer of pool rows, with ``lens`` valid tokens per row (the query sits
    at position lens - 1)."""
    last = jnp.asarray(lens) - 1
    return paged_decode_attention(
        q, pool_rows(kp), pool_rows(vp), 0, jnp.asarray(tables), last, last,
        scale=0.2, kv_heads=kp.shape[2], interpret=True)


@pytest.mark.parametrize("shape,bs", [
    ((2, 6, 2, 64, 64), 16),           # GQA g=3, hd=64: two tokens per row
    ((1, 4, 4, 64, 16), 64),           # MHA, hd=16: eight tokens per row
    ((3, 8, 2, 128, 64), 32),          # wider pool
])
def test_paged_kernel_vs_refs(shape, bs):
    b, hq, hkv, t, hd = shape
    n_pages = 2 * (b * t // bs)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, hd))
    k = jax.random.normal(ks[1], (b, hkv, t, hd))
    v = jax.random.normal(ks[2], (b, hkv, t, hd))
    lens = np.array([t - 3 * i for i in range(b)], np.int32)
    kp, vp, tables = _scatter_pages(k, v, lens, bs, n_pages)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    o = _pool_kernel(q.astype(jnp.bfloat16), kp, vp, tables, lens)
    r = paged_decode_attention_ref(q, kp, vp, jnp.asarray(tables),
                                   jnp.asarray(lens), scale=0.2)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r),
                               atol=2e-2, rtol=2e-2)
    # the paged path must agree with the CONTIGUOUS oracle row by row
    for row in range(b):
        rc = decode_attention_ref(q[row:row + 1], k[row:row + 1],
                                  v[row:row + 1], int(lens[row]), scale=0.2)
        np.testing.assert_allclose(np.asarray(o[row:row + 1], np.float32),
                                   np.asarray(rc), atol=2e-2, rtol=2e-2)


def test_paged_kernel_ignores_sentinel_table_entries():
    b, hq, hkv, t, hd, bs = 1, 2, 1, 64, 64, 16
    n_pages = 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, t, hd))
    v = jax.random.normal(ks[2], (b, hkv, t, hd))
    lens = np.array([17], np.int32)                # 2 of 4 pages valid
    kp, vp, tables = _scatter_pages(k, v, lens, bs, n_pages)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    o1 = _pool_kernel(q, kp, vp, tables, lens)
    garbage = tables.copy()
    garbage[0, 2:] = [0, n_pages + 1000]           # valid-range AND huge ids
    o2 = _pool_kernel(q, kp, vp, garbage, lens)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ------------------------------------------------------------ model forward
def test_make_paged_cache_rejects_non_attention():
    with pytest.raises(ValueError, match="pure-attention"):
        make_paged_cache(reduced(get_config("rwkv6-3b")), 8, 4)


def test_forward_paged_matches_contiguous(small_model):
    cfg, params = small_model
    b, max_len, bs = 2, 32, 8
    pool = b * (max_len // bs)
    prompts = [[5, 9, 2, 7, 1], [3, 8, 4, 4, 6, 2, 9, 1, 5]]

    cache = make_cache(cfg, b, max_len, src_len=1, dtype=cfg.cdtype)
    logits_c = []
    for i, p in enumerate(prompts):
        sub = jax.tree.map(
            lambda c: jnp.zeros_like(
                jax.lax.dynamic_slice_in_dim(c, i, 1, axis=1)), cache)
        lg, _, sub2 = forward(params, jnp.asarray([p]), cfg, cache=sub,
                              cache_index=jnp.zeros((), jnp.int32))
        cache = jax.tree.map(
            lambda c, s_: jax.lax.dynamic_update_slice_in_dim(
                c, s_.astype(c.dtype), i, axis=1), cache, sub2)
        logits_c.append(np.asarray(lg[0, len(p) - 1]))

    pcache = make_paged_cache(cfg, pool, bs, dtype=cfg.cdtype)
    tables = np.full((b, max_len // bs), pool + 5, np.int32)
    free = list(range(pool))
    logits_p = []
    for i, p in enumerate(prompts):     # chunked prefill, chunks of 4
        out, t0 = None, 0
        while t0 < len(p):
            chunk = p[t0:t0 + 4]
            while (tables[i] != pool + 5).sum() * bs < t0 + len(chunk):
                tables[i, (tables[i] != pool + 5).sum()] = free.pop(0)
            lg, _, pcache = forward(
                params, jnp.asarray([chunk]), cfg, cache=pcache,
                cache_index=jnp.asarray(t0, jnp.int32),
                block_tables=jnp.asarray(tables[i:i + 1]))
            out, t0 = lg[0, -1], t0 + len(chunk)
        logits_p.append(np.asarray(out))

    for lc, lp in zip(logits_c, logits_p):
        np.testing.assert_allclose(lc, lp, atol=1e-5, rtol=1e-5)

    # one batched decode step
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    toks = jnp.asarray([[int(lg.argmax())] for lg in logits_c], jnp.int32)
    lg_c, _, _ = forward(params, toks, cfg, cache=cache, lengths=lengths)
    lg_p, _, _ = forward(params, toks, cfg, cache=pcache, lengths=lengths,
                         block_tables=jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ pool rows
ROW_BS, ROW_MAX_LEN = 4, 16                       # 4 blocks per table row
ROW_TABLES = np.array([[5, 2, 9, 0], [7, 11, 3, 8]], np.int32)
ROW_PAGES = 12                                    # 1, 4, 6, 10: no row's
ROW_PROMPTS = [[5, 9, 2, 7, 1, 4, 8, 3, 6, 2, 7, 7, 1, 9, 4, 2],
               [3, 8, 4, 4, 6, 2, 9, 1, 5, 6, 3, 2, 8, 8, 5, 1]]


def _canonical(leaf, hkv, bs=ROW_BS):
    """A pool leaf (L, G, P, *row), read by its documented layout (one row
    of bs·(hkv/G)·w values per layer, head group and page, head-major) as
    (L, P, bs, hkv, w)."""
    leaf = np.asarray(leaf)
    n_l, n_g, n_p = leaf.shape[:3]
    w = leaf[0, 0, 0].size * n_g // (bs * hkv)
    leaf = leaf.reshape(n_l, n_g, n_p, hkv // n_g, bs, w)
    return leaf.transpose(0, 2, 4, 1, 3, 5).reshape(n_l, n_p, bs, hkv, w)


def _row_view(leaf, table, hkv):
    """(L, NB·bs, hkv, w): the tokens table row ``table`` maps."""
    c = _canonical(leaf, hkv)[:, table]
    return c.reshape(c.shape[0], -1, hkv, c.shape[-1])


def _contig_chunk(params, cfg, cache, row, toks, t0):
    sub = jax.tree.map(lambda c: c[:, row:row + 1], cache)
    lg, _, sub = forward(params, jnp.asarray([toks]), cfg, cache=sub,
                         cache_index=jnp.asarray(t0, jnp.int32))
    return lg[0], jax.tree.map(lambda c, s_: c.at[:, row:row + 1].set(s_),
                               cache, sub)


def _paged_chunk(params, cfg, pool, row, toks, t0):
    lg, _, pool = forward(params, jnp.asarray([toks]), cfg, cache=pool,
                          cache_index=jnp.asarray(t0, jnp.int32),
                          block_tables=jnp.asarray(ROW_TABLES[row:row + 1]))
    return lg[0], pool


# case: (tokens each row holds before the call, tokens the call writes)
ROW_CASES = {
    "decode": ((6, 9), 1),
    "aligned_chunk": ((4, 9), 8),          # row 0: blocks 1 and 2, whole
    "midpage_chunk": ((6, 9), 7),          # row 0: tokens 6..12, 3 blocks
    "verify_past_page_and_max_len": ((6, 14), 4),   # 6..9; 14..17 > 16
    "sentinel_row": ((6, 9), 1),           # row 1's table all sentinel
    "int8": ((6, 9), 1),
}


@pytest.mark.parametrize("case", list(ROW_CASES))
def test_paged_rows_match_contiguous(small_model, case):
    """Each kind of paged write, as whole page rows, against the
    contiguous cache: the same logits, the same K/V at every position the
    rows map, and every other page as it was."""
    cfg, params = small_model
    held, width = ROW_CASES[case]
    hkv = cfg.n_kv_heads
    kv_dtype = "int8" if case == "int8" else "bf16"
    ccache = make_cache(cfg, 2, ROW_MAX_LEN, src_len=1, dtype=cfg.cdtype)
    pool = make_paged_cache(cfg, ROW_PAGES, ROW_BS, dtype=cfg.cdtype,
                            kv_dtype=kv_dtype)
    for row, n in enumerate(held):
        _, ccache = _contig_chunk(params, cfg, ccache, row,
                                  ROW_PROMPTS[row][:n], 0)
        _, pool = _paged_chunk(params, cfg, pool, row,
                               ROW_PROMPTS[row][:n], 0)
    before = pool
    tables = ROW_TABLES.copy()
    rows = [0, 1]
    if case.endswith("chunk"):
        toks = ROW_PROMPTS[0][held[0]:held[0] + width]
        lc, ccache = _contig_chunk(params, cfg, ccache, 0, toks, held[0])
        lp, pool = _paged_chunk(params, cfg, pool, 0, toks, held[0])
        rows = [0]
    else:
        toks = jnp.asarray([[7 + r + j for j in range(width)]
                            for r in range(2)], jnp.int32)
        lengths = jnp.asarray(held, jnp.int32)
        if case == "sentinel_row":
            tables[1] = [ROW_PAGES, ROW_PAGES + 1000, ROW_PAGES, ROW_PAGES]
            rows = [0]
        lp, _, pool = forward(params, toks, cfg, cache=pool,
                              lengths=lengths,
                              block_tables=jnp.asarray(tables))
        sub = jax.tree.map(lambda c: c[:, rows[0]:rows[-1] + 1], ccache)
        lc, _, sub = forward(params, toks[rows[0]:rows[-1] + 1], cfg,
                             cache=sub, lengths=lengths[rows[0]:rows[-1] + 1])
        ccache = jax.tree.map(
            lambda c, s_: c.at[:, rows[0]:rows[-1] + 1].set(s_), ccache, sub)
        lp = lp[rows[0]:rows[-1] + 1]
    tol = 5e-2 if kv_dtype == "int8" else 1e-5
    np.testing.assert_allclose(np.asarray(lc), np.asarray(lp), atol=tol,
                               rtol=tol)

    for sl, c in ccache.items():
        p = pool[sl]["self"]
        for name in ("k", "v"):
            want = np.asarray(c["self"][name])          # (L, B, T, hkv, hd)
            for r in rows:
                n = min(held[r] + width, ROW_MAX_LEN)
                got = _row_view(p[f"{name}_pages"], ROW_TABLES[r], hkv)[:, :n]
                if kv_dtype == "int8":
                    scale = _row_view(p[f"{name}_scale"], ROW_TABLES[r],
                                      hkv)[:, :n, :, 0]
                    got = np.asarray(dequantize_kv(got, scale, jnp.float32))
                    # round trip: at most half a step of each entry's
                    # scale.  Layer 0's entries depend on the tokens alone;
                    # a later layer's on the quantized attention below it
                    assert (np.abs(got[0] - want[0, r, :n])
                            <= scale[0, ..., None] / 2 + 1e-6).all()
                else:
                    np.testing.assert_allclose(got, want[:, r, :n],
                                               atol=1e-6, rtol=1e-6)
        written = {int(pg) for r in rows for pg in ROW_TABLES[r]}
        for name, leaf in p.items():
            old = _canonical(before[sl]["self"][name], hkv)
            new = _canonical(leaf, hkv)
            for pg in set(range(ROW_PAGES)) - written:
                np.testing.assert_array_equal(new[:, pg], old[:, pg])


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_page_ops_move_whole_rows(small_model, kv_dtype):
    """zero_pages, copy_pages and the offload round trip act on one page
    of every leaf (all layers, all head groups) and on nothing else."""
    cfg, _ = small_model
    kv = PagedKVCache(cfg, num_blocks=6, block_size=ROW_BS,
                      max_len=ROW_MAX_LEN, kv_dtype=kv_dtype)
    keys = iter(jax.random.split(KEY, 8))
    pages = jax.tree.map(
        lambda p: (jax.random.normal(next(keys), p.shape) * 50
                   ).astype(p.dtype), kv.make_pages(groups=2))
    leaves = [np.asarray(x) for x in jax.tree.leaves(pages)]
    assert kv.block_bytes(pages, kv.num_blocks) == \
        sum(x.nbytes for x in leaves)

    def check(out, expect):
        for got, orig in zip(jax.tree.leaves(out), leaves):
            got = np.asarray(got)
            for pg in range(kv.num_blocks):
                src = expect.get(pg, pg)
                want = (np.zeros_like(orig[:, :, pg]) if src is None
                        else orig[:, :, src])
                np.testing.assert_array_equal(got[:, :, pg], want)

    check(kv.copy_pages(pages, 1, 4), {4: 1})
    zeroed = kv.zero_pages(pages, [0, 3])
    check(zeroed, {0: None, 3: None})
    host = kv.gather_host(pages, [2, 5])
    check(kv.scatter_host(zeroed, [0, 3], host), {0: 2, 3: 5})


def test_tp2_pool_holds_whole_heads_per_device():
    """tp=2 keeps two whole-head groups of the pool, one per device, and
    serves the same tokens as tp=1 with the same K/V in every page."""
    code = """
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.inference.engine import Request, ServeEngine
    from repro.models import init_params

    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    hkv, bs = cfg.n_kv_heads, 4

    def canonical(leaf):
        leaf = np.asarray(leaf)
        n_l, n_g, n_p = leaf.shape[:3]
        leaf = leaf.reshape(n_l, n_g, n_p, hkv // n_g, bs, -1)
        return leaf.transpose(0, 2, 4, 1, 3, 5).reshape(n_l, n_p, -1)

    engines, reqs = {}, {}
    for tp in (1, 2):
        eng = ServeEngine(cfg, params, max_batch=2, max_len=32, tp=tp,
                          cache="paged", block_size=bs, prefill_chunk=6)
        rng = np.random.default_rng(0)
        reqs[tp] = [Request(i, prompt=list(rng.integers(
            0, cfg.vocab_size, n)), max_new_tokens=8)
            for i, n in enumerate((11, 6))]
        for r in reqs[tp]:
            eng.submit(r)
        for _ in range(5):
            eng.tick()
        engines[tp] = eng
    one, two = (jax.tree.leaves(engines[t].cache) for t in (1, 2))
    for a, b in zip(one, two):
        assert a.shape[1] == 1 and b.shape[1] == 2
        for shard in b.addressable_shards:
            g = shard.index[1].start
            assert shard.data.shape == (b.shape[0], 1) + b.shape[2:]
            np.testing.assert_array_equal(np.asarray(shard.data),
                                          np.asarray(b)[:, g:g + 1])
        np.testing.assert_allclose(canonical(a), canonical(b), atol=1e-6)
    for eng in engines.values():
        while eng.tick():
            pass
    toks = {t: [r.generated for r in rs] for t, rs in reqs.items()}
    assert toks[1] == toks[2] and all(len(g) == 8 for g in toks[1])
    print("WHOLE_HEADS_OK")
    """
    repo = Path(__file__).resolve().parents[1]
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": str(repo / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=str(repo), timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "WHOLE_HEADS_OK" in out.stdout


# ------------------------------------------------------------ engine
def test_engine_paged_matches_contiguous_tokens(small_model):
    cfg, params = small_model
    e1 = ServeEngine(cfg, params, max_batch=2, max_len=32)
    t1 = _tokens(e1.run(_mk_requests(cfg)))
    e2 = ServeEngine(cfg, params, max_batch=2, max_len=32,
                     cache="paged", block_size=8)
    t2 = _tokens(e2.run(_mk_requests(cfg)))
    assert t1 == t2
    assert e2.stats.preemptions == 0
    assert e2.stats.peak_block_pool_utilization > 0


def test_chunked_prefill_matches_unchunked(small_model):
    cfg, params = small_model
    whole = ServeEngine(cfg, params, max_batch=2, max_len=32,
                        cache="paged", block_size=8)
    t_whole = _tokens(whole.run(_mk_requests(cfg)))
    chunked = ServeEngine(cfg, params, max_batch=2, max_len=32,
                          cache="paged", block_size=8, prefill_chunk=4)
    t_chunk = _tokens(chunked.run(_mk_requests(cfg)))
    assert t_whole == t_chunk
    # the longest prompt (16 tokens) must have been split into 4 chunks
    assert chunked.stats.prefill_chunks > chunked.stats.prefills


@pytest.mark.parametrize("offload", ["none", "host"])
def test_preemption_resume_byte_identical(small_model, offload):
    """Satellite: exhaust the block pool, assert evicted requests resume
    and final tokens match an unconstrained run byte-for-byte."""
    cfg, params = small_model
    free = ServeEngine(cfg, params, max_batch=2, max_len=32,
                       cache="paged", block_size=4)
    t_free = _tokens(free.run(_mk_requests(cfg)))
    tight = ServeEngine(cfg, params, max_batch=2, max_len=32,
                        cache="paged", block_size=4, num_blocks=6,
                        offload=offload)
    done = tight.run(_mk_requests(cfg))
    assert _tokens(done) == t_free
    assert tight.stats.preemptions > 0
    assert all(r.status == "done" for r in done)
    if offload == "host":
        assert tight.stats.offload_bytes > 0
        assert tight.stats.offload_bytes == tight.stats.restore_bytes
        assert tight.stats.modeled_offload_tax_s > 0
    else:
        assert tight.stats.offload_bytes == 0


def test_paged_engine_reset_reproduces(small_model):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32,
                      cache="paged", block_size=4, num_blocks=6,
                      offload="host")
    t1 = _tokens(eng.run(_mk_requests(cfg)))
    eng.reset()
    assert eng.stats.preemptions == 0 and eng.kv.pool.used_blocks == 0
    t2 = _tokens(eng.run(_mk_requests(cfg)))
    assert t1 == t2


def test_decode_stall_during_prefill_contention_recovers(small_model):
    """A decode row that cannot grow while in-flight prefills hold the
    pool must stall and retry, not crash — only a true deadlock raises."""
    cfg, params = small_model
    reqs = dict(n=5, base_plen=6, max_new=6)
    free = ServeEngine(cfg, params, max_batch=3, max_len=32,
                       cache="paged", block_size=4)
    t_free = _tokens(free.run(_mk_requests(cfg, **reqs)))
    tight = ServeEngine(cfg, params, max_batch=3, max_len=32,
                        cache="paged", block_size=4, num_blocks=7,
                        prefill_chunk=3)
    done = tight.run(_mk_requests(cfg, **reqs))
    assert _tokens(done) == t_free
    assert all(r.status == "done" for r in done)


def test_pool_too_small_raises(small_model):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=1, max_len=32,
                      cache="paged", block_size=4, num_blocks=2)
    with pytest.raises(RuntimeError, match="pool"):
        eng.run(_mk_requests(cfg, n=1, base_plen=12, max_new=8))


# ------------------------------------------------------------ admit guard
@pytest.mark.parametrize("cache", ["contiguous", "paged"])
def test_admit_rejects_overflowing_budget(small_model, cache):
    """Satellite: plen + budget > max_len is rejected up front instead of
    risking out-of-bounds KV writes."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, cache=cache)
    bad = Request(0, prompt=list(range(1, 30)), max_new_tokens=16)  # 29+16
    ok = Request(1, prompt=list(range(1, 28)), max_new_tokens=5)    # 27+5=32
    done = eng.run([bad, ok])
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].status == "rejected" and by_rid[0].generated == []
    assert by_rid[1].status == "done"
    assert len(by_rid[1].generated) == 5
    assert eng.stats.rejected == 1
    # the rejected request never touched a slot or the KV cache
    assert eng.stats.prefills == 1


# ------------------------------------------------------------ validation
def test_engine_rejects_bad_cache_config(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError, match="cache"):
        ServeEngine(cfg, params, cache="virtual")
    with pytest.raises(ValueError, match="offload"):
        ServeEngine(cfg, params, cache="paged", offload="disk")
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeEngine(cfg, params, cache="paged", prefill_chunk=0)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, offload="host")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, prefill_chunk=8)


# ------------------------------------------------------------ offload pricing
def test_offload_cost_lc_vs_cc():
    lc, cc = PLATFORMS["Intel+H100"], PLATFORMS["GH200"]
    nbytes = 1 << 20
    assert offload_cost_s(lc, nbytes) > offload_cost_s(cc, nbytes)
    assert offload_cost_s(lc, 0, transfers=2) == \
        pytest.approx(2 * lc.link_lat_s)
    with pytest.raises(ValueError):
        offload_cost_s(lc, -1)


def test_host_offload_tier_accounting():
    tier = HostOffloadTier("Intel+H100")
    leaves = [np.ones((2, 3, 4), np.float32)]
    nbytes, tax = tier.evict("r0", leaves, n_blocks=3)
    assert nbytes == leaves[0].nbytes and tier.holds("r0")
    assert tier.stored_blocks("r0") == 3
    assert tax == pytest.approx(
        offload_cost_s(tier.spec, nbytes, transfers=3))
    back, n_blocks, rbytes, rtax = tier.restore("r0")
    assert n_blocks == 3 and rbytes == nbytes and not tier.holds("r0")
    assert rtax > 0
    np.testing.assert_array_equal(back[0], leaves[0])
    assert tier.modeled_tax_s == pytest.approx(tax + rtax)
    tier.clear()
    assert tier.offload_bytes == 0


def test_memory_pressure_sweep_lc_vs_cc(small_model):
    """Acceptance: measured offload tax differs between an LC (PCIe) and
    CC (C2C) device model.  Closed-loop scenario -> identical traffic."""
    cfg, params = small_model
    sweep = memory_pressure_sweep(
        cfg, params, scenario="summarization", platforms=("AMD+A100",
                                                          "GH200"),
        pool_fracs=(0.4,), max_batch=2, max_len=32, block_size=4,
        n_requests=4, seed=0, prompt_cap=12, output_cap=6)
    lc, cc = sweep["points"]
    assert lc["coupling"] == "LC" and cc["coupling"] == "CC"
    assert lc["preemptions"] > 0
    # identical measured traffic (closed-loop determinism) ...
    assert lc["offload_bytes"] == cc["offload_bytes"] > 0
    assert lc["preemptions"] == cc["preemptions"]
    # ... but the LC link prices it much higher
    assert lc["modeled_offload_tax_us"] > 2 * cc["modeled_offload_tax_us"]


# ------------------------------------------------------------ refcounts / CoW
def test_block_pool_refcount_conservation_with_sharing():
    """alloc == free with sharing in between: every block physically
    freed exactly once, refcounts sum to the owned-list entries."""
    pool = BlockPool(8, 4)
    a = pool.alloc("a", 4)
    pool.adopt("b", a[:2])
    pool.adopt("c", a[:2])
    assert pool.ref_count(a[0]) == 3 and pool.ref_count(a[2]) == 1
    assert pool.shared_blocks == 2 and pool.extra_refs == 4
    total_refs = sum(pool.ref_count(i) for i in range(8))
    total_owned = sum(len(pool.owned(o)) for o in pool.owners())
    assert total_refs == total_owned == 8
    # the donor draining does NOT free shared blocks...
    assert pool.free("a") == a[2:]
    assert pool.used_blocks == 2 and pool.ref_count(a[0]) == 2
    # ...nor does the first sharer...
    assert pool.free("b") == []
    assert pool.shared_blocks == 0
    # ...only the LAST reference frees physically
    assert pool.free("c") == a[:2]
    assert pool.used_blocks == 0 and pool.free_blocks == 8


def test_block_pool_adopt_and_cow_validation():
    pool = BlockPool(4, 2)
    a = pool.alloc("a", 2)
    with pytest.raises(ValueError):
        pool.adopt("b", [3])               # free block: not adoptable
    pool.adopt("b", a)
    with pytest.raises(ValueError):
        pool.cow("b", 5)                   # no block at that index
    old, new = pool.cow("b", 0)
    assert old == a[0] and new not in a
    assert pool.owned("b") == [new, a[1]]
    assert pool.owned("a") == a            # donor list untouched
    assert pool.ref_count(old) == 1 and pool.ref_count(new) == 1
    assert pool.cow_copies_total == 1
    with pytest.raises(ValueError):
        pool.cow("b", 0)                   # private now: cow is a no-op
    pool.alloc("c", 1)                     # pool full
    with pytest.raises(MemoryError):
        pool.cow("b", 1)                   # a[1] still shared, no free block


def test_block_pool_trim_is_refcount_aware():
    """Spec-rollback trim of a sharer must not zero pages the donor still
    reads (trim returns only physically-freed ids)."""
    pool = BlockPool(8, 4)
    a = pool.alloc("a", 3)
    pool.adopt("b", a)                     # b shares all of a's blocks
    assert pool.trim("b", 4) == []         # drops 2 shared refs, frees none
    assert pool.owned("b") == a[:1]
    assert pool.ref_count(a[2]) == 1       # back to donor-private
    pool.free("a")
    assert pool.trim("b", 0) == a[:1]      # now the last ref frees


def test_block_pool_shared_metrics_families():
    from repro.telemetry.registry import MetricsRegistry
    reg = MetricsRegistry()
    pool = BlockPool(8, 4)
    pool.block_bytes = 100
    pool.bind_metrics(reg)
    a = pool.alloc("a", 2)
    pool.adopt("b", a)
    snap = {name: reg.get(name).series()[()] for name in
            ("kv_shared_blocks", "kv_cow_copies_total", "kv_bytes_saved")}
    assert snap["kv_shared_blocks"] == 2
    assert snap["kv_bytes_saved"] == 200   # 2 extra refs x block_bytes
    assert snap["kv_cow_copies_total"] == 0
    assert pool.peak_shared_blocks == 2
    pool.cow("b", 0)
    assert reg.get("kv_cow_copies_total").series()[()] == 1
    assert reg.get("kv_shared_blocks").series()[()] == 1
    pool.free("a")
    pool.free("b")
    assert reg.get("kv_shared_blocks").series()[()] == 0
    assert pool.peak_shared_blocks == 2    # high-water mark survives


def _mk_shared_requests(cfg, n=6, head=24, max_new=6):
    """Same sampled system prompt + per-request tail.  Closed loop (all
    arrivals at 0) keeps scheduling independent of measured step times;
    rid 0 decodes 3x longer, so it is still live — a valid donor — when
    slots free up for the requests beyond max_batch."""
    rng = np.random.default_rng(7)
    sys_prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, head)]
    return [Request(rid, prompt=sys_prompt +
                    [int(t) for t in rng.integers(1, cfg.vocab_size,
                                                  4 + rid)],
                    max_new_tokens=(3 * max_new) if rid == 0 else max_new)
            for rid in range(n)]


def test_prefix_sharing_byte_identical_and_refcounts(small_model):
    """Acceptance: CoW prefix sharing with quantization OFF emits tokens
    byte-identical to the unshared paged run; adoption fires; refcounts
    conserve (pool drains to zero)."""
    cfg, params = small_model
    base = ServeEngine(cfg, params, max_batch=4, max_len=96, cache="paged",
                       block_size=8, prefill_chunk=8)
    t_base = _tokens(base.run(_mk_shared_requests(cfg)))
    shared = ServeEngine(cfg, params, max_batch=4, max_len=96,
                         cache="paged", block_size=8, prefill_chunk=8,
                         share_prefix=True)
    t_shared = _tokens(shared.run(_mk_shared_requests(cfg)))
    assert t_shared == t_base
    assert shared.stats.prefix_adoptions > 0
    assert shared.stats.shared_prefix_tokens > 0
    assert shared.kv.pool.peak_shared_blocks > 0
    # all references released: the pool drains to zero with no leaks
    assert shared.kv.pool.used_blocks == 0
    assert shared.kv.pool._refs == {}
    assert shared.kv.pool.free_blocks == shared.kv.num_blocks


@pytest.mark.parametrize("offload", ["none", "host"])
def test_prefix_sharing_survives_preempt_and_offload(small_model, offload):
    """Acceptance: sharing stays byte-identical across preempt/recompute
    and host-offload/restore — evicting a sharer never corrupts a block
    the donor still reads (physical frees only on last ref)."""
    cfg, params = small_model
    free_eng = ServeEngine(cfg, params, max_batch=3, max_len=64,
                           cache="paged", block_size=8, prefill_chunk=8)
    t_free = _tokens(free_eng.run(_mk_shared_requests(cfg, max_new=4)))
    tight = ServeEngine(cfg, params, max_batch=3, max_len=64,
                        cache="paged", block_size=8, prefill_chunk=8,
                        share_prefix=True, num_blocks=9, offload=offload)
    done = tight.run(_mk_shared_requests(cfg, max_new=4))
    assert _tokens(done) == t_free
    assert all(r.status == "done" for r in done)
    assert tight.stats.preemptions > 0
    assert tight.stats.prefix_adoptions > 0
    assert tight.kv.pool.used_blocks == 0 and tight.kv.pool._refs == {}


def test_prefix_sharing_with_quantization_stacks(small_model):
    """int8 + share_prefix together: the shared-vs-unshared comparison is
    still byte-identical AT THE SAME kv_dtype (quantized pages are shared
    bit-exactly, so adoption adds no extra quantization error)."""
    cfg, params = small_model
    outs = {}
    for share in (False, True):
        eng = ServeEngine(cfg, params, max_batch=4, max_len=96,
                          cache="paged", block_size=8, prefill_chunk=8,
                          kv_dtype="int8", share_prefix=share)
        outs[share] = _tokens(eng.run(_mk_shared_requests(cfg)))
        if share:
            assert eng.stats.prefix_adoptions > 0
    assert outs[True] == outs[False]


def test_cow_write_divergence_preserves_donor_pages(small_model):
    """Direct CoW exercise: force a sharer to diverge mid-sequence via
    _cow_protect and check the donor's page contents are preserved and
    the writer got a private copy."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, cache="paged",
                      block_size=4, share_prefix=True)
    pool = eng.kv.pool
    ids = pool.alloc("donor", 2)
    eng.cache = jax.tree.map(lambda p: p.at[:, :, ids[0]].set(1), eng.cache)
    pool.adopt("writer", ids)
    assert pool.shared_blocks == 2
    # writer is about to write tokens [0, 4): block 0 must diverge
    assert eng._cow_protect("writer", 0, 4)
    assert pool.cow_copies_total == 1
    w = pool.owned("writer")
    assert w[0] != ids[0] and w[1] == ids[1]
    # the copied page carries the donor's contents
    leaf = jax.tree.leaves(eng.cache)[0]
    np.testing.assert_array_equal(np.asarray(leaf[:, :, w[0]]),
                                  np.asarray(leaf[:, :, ids[0]]))
    assert np.asarray(leaf[:, :, w[0]]).min() == 1
    # donor's view is untouched and still shared on block 1 only
    assert pool.owned("donor") == ids
    assert pool.ref_count(ids[0]) == 1 and pool.ref_count(ids[1]) == 2
