"""The Pallas paged decode kernel (``paged_decode_attention``, run in the
interpreter) against the XLA read of ``_paged_attention_fwd`` it replaces
on the TPU: layer by layer over shuffled pool pages, and through a whole
served decode step.  Also which path each kind of paged call takes, and
the ``repro.decode`` span's page counts."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.backends.bodies import make_step_bodies
from repro.inference.engine import Request, ServeEngine
from repro.kernels.decode_attention import ops
from repro.kernels.decode_attention.layout import row_shape
from repro.kernels.decode_attention.ops import (decode_kernel_fits,
                                                paged_decode_attention)
from repro.kvcache import PagedKVCache
from repro.layers import attention
from repro.models import init_params

KERNEL = functools.partial(paged_decode_attention, interpret=True)
BF16 = {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


def _kernel_on(monkeypatch, calls=None):
    """Route decode calls through the interpreted kernel wherever it fits
    (off the TPU the program keeps the XLA read); ``calls`` gathers the
    pools of the calls that take it."""
    def choose(pool, tables, n_kv, hd):
        if not decode_kernel_fits(pool, tables, n_kv, hd):
            return None
        if calls is not None:
            calls.append(pool.shape)
        return KERNEL
    monkeypatch.setattr(attention, "_decode_kernel", choose)


def _cfg(hq, hkv, hd, cap=0.0, **kw):
    return reduced(get_config("smollm-360m"), n_heads=hq, n_kv_heads=hkv,
                   head_dim=hd, attn_softcap=cap, **BF16, **kw)


def _tables(rng, lengths, n_pages, nb, bs, sentinel):
    """Shuffled, non-contiguous pages for each row's tokens up to and
    including the one this step writes; -1 lengths mark all-sentinel
    rows."""
    perm = iter(rng.permutation(n_pages))
    bt = np.full((len(lengths), nb), sentinel, np.int32)
    for r, n in enumerate(lengths):
        if n >= 0:
            bt[r, :n // bs + 1] = [next(perm) for _ in range(n // bs + 1)]
    return bt


def _reference(q, pool_k, pool_v, bt, lengths, hkv, bs, scale, window, cap):
    """Float64 decode attention over the pages each row's table maps."""
    def view(pool):                          # (P, bs, hkv, hd) canonical
        p = np.asarray(pool[1], np.float64)  # layer 1, (G, P, *row)
        n_g, n_p = p.shape[:2]
        p = p.reshape(n_g, n_p, hkv // n_g, bs, -1)
        return p.transpose(1, 3, 0, 2, 4).reshape(n_p, bs, hkv, -1)
    k, v = view(pool_k), view(pool_v)
    qf = np.asarray(q, np.float64)
    g = qf.shape[1] // hkv
    out = np.zeros(qf.shape)
    for b, n in enumerate(lengths):
        kk = k[bt[b, :n // bs + 1]].reshape(-1, hkv, k.shape[-1])[:n + 1]
        vv = v[bt[b, :n // bs + 1]].reshape(-1, hkv, v.shape[-1])[:n + 1]
        t = np.arange(n + 1)
        keep = (n - t < window) if window else np.ones(n + 1, bool)
        for h in range(qf.shape[1]):
            s = kk[:, h // g] @ qf[b, h] * scale
            if cap:
                s = cap * np.tanh(s / cap)
            s = np.where(keep, s, -np.inf)
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ vv[:, h // g]
    return out


# (query heads, KV heads, head groups, head dim, block size, window, cap)
CASES = {
    "gqa3_g1_hd64": (15, 5, 1, 64, 16, 0, 0.0),
    "gqa3_g2_hd64": (6, 2, 2, 64, 16, 0, 0.0),
    "mha_g1_hd64": (4, 4, 1, 64, 16, 0, 0.0),
    "mha_g2_hd128": (4, 4, 2, 128, 8, 0, 0.0),
    "gqa8_one_kv_head_hd128": (8, 1, 1, 128, 8, 0, 0.0),
    "window": (6, 2, 1, 64, 16, 20, 0.0),
    "softcap": (6, 2, 1, 64, 16, 0, 5.0),
    "row_tiles": (9, 3, 1, 64, 16, 0, 0.0),
}
# cases whose rows the kernel takes this many per grid step (the rest take
# every row in one): 8 rows as 3 tiles of 3, the last one padded
TILE_ROWS = {"row_tiles": 3}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_kernel_matches_xla_read(monkeypatch, case):
    """Rows of length 0, 1, 15, 16, 17 and max_len - 1 on shuffled pages,
    and two all-sentinel rows with stale lengths: the kernel's output is
    the XLA read's within bf16 rounding (and both are the float64
    reference's), a sentinel row reads nothing, and the pool is written
    alike; also where the rows take several grid steps."""
    hq, hkv, n_groups, hd, bs, window, cap = CASES[case]
    tiles = []
    if case in TILE_ROWS:
        heads, qrows = hkv // n_groups, -(-(128 // hd) * hq // hkv // 8) * 8
        monkeypatch.setattr(ops, "ROWS_VMEM",
                            TILE_ROWS[case] * 6 * heads * qrows * 128 * 4)
        inner = ops.paged_decode_attention_kernel

        def kernel(*args, **kw):
            tiles.append(kw["tile"])
            return inner(*args, **kw)
        monkeypatch.setattr(ops, "paged_decode_attention_kernel", kernel)
    cfg = _cfg(hq, hkv, hd, cap)
    nb = 64 // bs
    live = [0, 1, 15, 16, 17, nb * bs - 1]
    n_pages = len(live) * nb + 3
    rng = np.random.default_rng(hash(case) % 2**32)
    bt = _tables(rng, live + [-1, -1], n_pages, nb, bs, n_pages)
    bt[-1] = n_pages + 1000
    lengths = np.array(live + [5, nb * bs - 1], np.int32)
    b = len(lengths)
    ks = iter(jax.random.split(jax.random.PRNGKey(len(case)), 5))
    shape = (2, n_groups, n_pages) + row_shape(bs, hkv // n_groups, hd)
    pool = {n: jax.random.normal(next(ks), shape).astype(jnp.bfloat16)
            for n in ("k_pages", "v_pages")}
    q, k, v = (jax.random.normal(next(ks), (b, 1, h, hd)).astype(jnp.bfloat16)
               for h in (hq, hkv, hkv))
    scale = hd ** -0.5
    args = (q, k, v, pool, jnp.int32(1), jnp.asarray(bt),
            jnp.asarray(lengths)[:, None], jnp.asarray(lengths), None, cfg)
    kw = dict(causal=True, window=window, scale=scale)
    o_xla, pool_xla = attention._paged_attention_fwd(*args, **kw)
    calls = []
    _kernel_on(monkeypatch, calls)
    o_ker, pool_ker = attention._paged_attention_fwd(*args, **kw)
    assert calls, "the decode call did not ask for the kernel"
    assert tiles == ([TILE_ROWS[case]] if case in TILE_ROWS else [])

    n = len(live)
    o_ker = np.asarray(o_ker[:, 0], np.float32)
    np.testing.assert_allclose(o_ker[:n], np.asarray(o_xla[:n, 0],
                                                     np.float32),
                               atol=2e-2, rtol=2e-2)
    ref = _reference(q[:n, 0], pool_ker["k_pages"], pool_ker["v_pages"], bt,
                     live, hkv, bs, scale, window, cap)
    np.testing.assert_allclose(o_ker[:n], ref, atol=2e-2, rtol=2e-2)
    assert not o_ker[n:].any()
    for name in pool:
        np.testing.assert_array_equal(np.asarray(pool_ker[name]),
                                      np.asarray(pool_xla[name]))


def test_served_decode_step_kernel_matches_xla(monkeypatch):
    """``make_step_bodies(...).paged_decode`` with the kernel in place of
    the XLA read: the same logits within bf16 rounding for every stepped
    row, and the same pool: layer 0 and every page the step does not
    write identical, the new entries of later layers (computed from the
    attention below them) within bf16 rounding."""
    cfg = _cfg(6, 2, 64, n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kv = PagedKVCache(cfg, num_blocks=40, block_size=16, max_len=64)
    pages = kv.make_pages()
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    pages = jax.tree.map(lambda p: jax.random.normal(
        next(keys), p.shape).astype(p.dtype), pages)
    live = [0, 1, 15, 16, 17, 63]
    rng = np.random.default_rng(7)
    bt = _tables(rng, live + [-1], 40, kv.nb_per_slot, 16, kv.sentinel)
    assert decode_kernel_fits(jax.tree.leaves(pages)[0], bt, 2, 64)
    lengths = jnp.asarray(live + [30], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(live) + 1, 1)),
                       jnp.int32)

    def step():
        body = make_step_bodies(cfg).paged_decode
        return jax.jit(body)(params, pages, toks, lengths, jnp.asarray(bt))

    lg_xla, pool_xla = step()
    _kernel_on(monkeypatch)
    lg_ker, pool_ker = step()
    n = len(live)
    np.testing.assert_allclose(np.asarray(lg_ker[:n], np.float32),
                               np.asarray(lg_xla[:n], np.float32),
                               atol=5e-2, rtol=5e-2)
    written = [int(bt[r, n // 16]) for r, n in enumerate(live)]
    kept = np.setdiff1d(np.arange(40), written)
    for a, b in zip(jax.tree.leaves(pool_ker), jax.tree.leaves(pool_xla)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[:, :, kept], b[:, :, kept])
        np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("kind,asks", [
    ("decode", True), ("decode_window", True), ("decode_softcap", True),
    ("verify", False), ("prefill_chunk", False), ("int8_decode", False)])
def test_which_paged_calls_take_the_kernel(monkeypatch, kind, asks):
    """Decode calls on a bf16 pool (windowed and soft-capped ones too) run
    the kernel; the speculative verify (S = k+1), prefill chunks (no
    lengths) and the int8 pool keep the XLA read."""
    cfg = _cfg(6, 2, 64, 5.0 if kind == "decode_softcap" else 0.0)
    kv_dtype = "int8" if kind.startswith("int8") else "bf16"
    kv = PagedKVCache(cfg, num_blocks=12, block_size=16, max_len=64,
                      kv_dtype=kv_dtype)
    pool = kv.make_pages()["slot0"]["self"]
    s = {"verify": 3, "prefill_chunk": 8}.get(kind, 1)
    b = 1 if kind == "prefill_chunk" else 2
    ks = iter(jax.random.split(jax.random.PRNGKey(2), 3))
    q, k, v = (jax.random.normal(next(ks), (b, s, h, 64)).astype(jnp.bfloat16)
               for h in (6, 2, 2))
    bt = jnp.asarray(np.arange(4 * b).reshape(b, 4), jnp.int32)
    lengths = None if kind == "prefill_chunk" else jnp.asarray([5, 20][:b])
    base = jnp.zeros((b, 1), jnp.int32) if lengths is None \
        else lengths[:, None]
    positions = base + jnp.arange(s, dtype=jnp.int32)
    calls = []
    _kernel_on(monkeypatch, calls)
    out, _ = attention._paged_attention_fwd(
        q, k, v, pool, 0, bt, positions, lengths, jnp.int32(0), cfg,
        causal=True, window=20 if kind == "decode_window" else 0,
        scale=0.125)
    assert out.shape == q.shape
    assert bool(calls) == asks


def test_off_the_tpu_decode_keeps_the_xla_read():
    """Unpatched, the program picks the kernel only where Pallas compiles
    for the device: on the CPU every decode call keeps the XLA read."""
    pool = jnp.zeros((2, 1, 8) + row_shape(16, 2, 64), jnp.bfloat16)
    tables = jnp.zeros((2, 4), jnp.int32)
    assert decode_kernel_fits(pool, tables, 2, 64)
    assert attention._decode_kernel(pool, tables, 2, 64) is None


@pytest.mark.parametrize("shape,dtype,n_kv,hd,fits", [
    ((32, 1, 8192) + row_shape(16, 5, 64), jnp.bfloat16, 5, 64, True),
    ((32, 1, 4096) + row_shape(16, 1, 128), jnp.bfloat16, 4, 128, True),
    ((2, 1, 8) + row_shape(16, 2, 64), jnp.float32, 2, 64, False),
    ((2, 1, 8) + row_shape(4, 2, 16), jnp.bfloat16, 2, 16, False),
    ((2, 1, 8) + row_shape(16, 2, 256), jnp.bfloat16, 2, 256, False),
    ((2, 1, 8) + row_shape(16, 2, 112), jnp.bfloat16, 2, 112, False),
])
def test_decode_kernel_fits_only_tiled_bf16_rows(shape, dtype, n_kv, hd,
                                                 fits):
    tables = jax.ShapeDtypeStruct((64, 128), jnp.int32)
    pool = jax.ShapeDtypeStruct(shape, dtype)
    assert decode_kernel_fits(pool, tables, n_kv, hd) == fits


@pytest.mark.parametrize("rows,entries,fits", [
    (64, 128, True), (512, 128, True), (1024, 128, False), (64, 4096, False)])
def test_decode_kernel_fits_tables_in_scalar_memory(rows, entries, fits):
    """The kernel prefetches whole block tables into scalar memory: tables
    past its budget (1024 rows of a 2048-token context, or 64 rows of a
    65536-token one at 16 tokens a page) keep the XLA read."""
    pool = jax.ShapeDtypeStruct((32, 1, 8192) + row_shape(16, 5, 64),
                                jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((rows, entries), jnp.int32)
    assert decode_kernel_fits(pool, tables, 5, 64) == fits


def test_decode_span_counts_live_and_table_pages(monkeypatch):
    """Under the profiler the ``repro.decode`` span reports ``kv_pages``,
    the pages the stepped rows read, and ``table_pages``, B x NB."""
    import repro.inference.engine as engine_mod
    recorded = []

    class Span:
        def __init__(self, name, **args):
            self.name, self.args = name, dict(args)

        def set_metadata(self, **args):
            self.args.update(args)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            recorded.append((self.name, self.args))

    monkeypatch.setattr(engine_mod, "profiling", lambda: True)
    monkeypatch.setattr(engine_mod, "profiler_span", Span)
    cfg = reduced(get_config("smollm-360m"), n_layers=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    bs, max_len = 4, 32
    eng = ServeEngine(cfg, params, max_batch=2, max_len=max_len,
                      cache="paged", block_size=bs)
    prompts = (5, 9)
    eng.run([Request(i, prompt=list(range(1, n + 1)), max_new_tokens=4)
             for i, n in enumerate(prompts)])
    decode = [a for name, a in recorded if name == "decode" and "rows" in a]
    assert decode
    nb = max_len // bs
    assert all(a["table_pages"] == 2 * nb for a in decode)
    assert all(a["kv_pages"] >= a["rows"] for a in decode)
    # each row reads the pages of its held tokens and the new one
    want = sum(-(-(n + k + 1) // bs) for n in prompts for k in range(3))
    assert sum(a["kv_pages"] for a in decode) == want
