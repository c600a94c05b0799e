"""Quantized KV cache: per-(token, head) int8 quantization properties
(round-trip bound, degenerate inputs, jit dtype stability), quantized
paged Pallas kernel vs oracles, quantized forward/engine tolerance vs the
bf16 paged path, and the byte-budget capacity gain the quantization buys
(admission capacity / pool utilization acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config, reduced
from repro.inference.engine import Request, ServeEngine
from repro.inference.kv_quant import (KV_DTYPES, capacity_ratio,
                                      dequantize_kv, kv_entry_bytes,
                                      make_quantized_cache, quantize_kv,
                                      read_kv, write_kv)
from repro.kernels.decode_attention.ops import (paged_decode_attention,
                                                paged_decode_attention_quant)
from repro.kernels.decode_attention.ref import (
    paged_decode_attention_quant_ref, paged_decode_attention_ref, pool_rows)
from repro.kvcache import default_num_blocks
from repro.models import forward, init_params, make_paged_cache
from repro.telemetry.characterize import memory_pressure_sweep

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(KEY, cfg)
    return cfg, params


# ------------------------------------------------------------ quant math
def test_entry_bytes_and_capacity_ratio():
    assert kv_entry_bytes(64) == 128
    assert kv_entry_bytes(64, "int8") == 68
    assert capacity_ratio(64) == pytest.approx(128 / 68)
    # the ratio grows toward 2x as hd grows (the 4-byte scale amortizes)
    assert capacity_ratio(16) < capacity_ratio(64) < capacity_ratio(256) < 2
    with pytest.raises(ValueError):
        kv_entry_bytes(64, "fp8")


def _roundtrip_bound(x):
    """Round-trip |x - deq(quant(x))| <= scale/2 element-wise (symmetric
    rounding), with scale the per-(token, head) row scale."""
    q, scale = quantize_kv(x)
    back = dequantize_kv(q, scale, jnp.float32)
    err = np.abs(np.asarray(x, np.float32) - np.asarray(back))
    bound = np.asarray(scale)[..., None] / 2 + 1e-7
    assert (err <= bound).all(), (err.max(), bound.min())


def test_quant_roundtrip_bound_seeded():
    for i, shape in enumerate([(8, 16), (2, 5, 3, 32), (1, 64)]):
        x = jax.random.normal(jax.random.PRNGKey(i), shape) * (10.0 ** i)
        _roundtrip_bound(x)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, width=32),
                min_size=4, max_size=64))
def test_quant_roundtrip_bound_property(row):
    _roundtrip_bound(jnp.asarray([row], jnp.float32))


def test_quant_zero_rows_exact():
    """All-zero rows must quantize to exact zeros (scale floors at 1e-8,
    never divides by zero) — zero-filled fresh cache pages stay zero."""
    q, scale = quantize_kv(jnp.zeros((3, 4, 16)))
    assert np.asarray(q).dtype == np.int8 and not np.asarray(q).any()
    assert (np.asarray(scale) > 0).all()
    back = dequantize_kv(q, scale, jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), 0.0)


def test_quant_denormal_rows_bounded():
    """Sub-floor magnitudes (denormal-scale inputs) hit the 1e-8 scale
    floor: they round to zero payloads with error below the floor."""
    x = jnp.full((2, 8), 1e-30, jnp.float32)
    q, scale = quantize_kv(x)
    assert not np.asarray(q).any()
    assert np.asarray(scale) == pytest.approx(1e-8 / 127.0)
    _roundtrip_bound(x)


def test_quant_dtype_stability_under_jit():
    x = jax.random.normal(KEY, (4, 3, 16), jnp.bfloat16)
    qe, se = quantize_kv(x)
    qj, sj = jax.jit(quantize_kv)(x)
    assert qj.dtype == qe.dtype == jnp.int8
    assert sj.dtype == se.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(qe), np.asarray(qj))
    np.testing.assert_array_equal(np.asarray(se), np.asarray(sj))
    for dt in (jnp.bfloat16, jnp.float32):
        assert dequantize_kv(qe, se, dt).dtype == dt
        assert jax.jit(dequantize_kv, static_argnums=2)(qe, se, dt).dtype \
            == dt


def test_write_read_roundtrip_contiguous_helper():
    cache = make_quantized_cache(2, 8, 3, 16)
    k = jax.random.normal(KEY, (2, 4, 3, 16))
    v = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 3, 16))
    cache = write_kv(cache, k, v, 2)
    kb, vb = read_kv(cache, jnp.float32)
    _, sk = quantize_kv(k)
    err = np.abs(np.asarray(k) - np.asarray(kb[:, 2:6]))
    assert (err <= np.asarray(sk)[..., None] / 2 + 1e-7).all()
    assert not np.asarray(kb[:, :2]).any() and not np.asarray(vb[:, 6:]).any()


# ------------------------------------------------------------ kernel
def _quant_pool(b, hq, hkv, t, hd, bs, seed=0):
    n_pages = 2 * (b * t // bs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, hd))
    k = jax.random.normal(ks[1], (b, hkv, t, hd))
    v = jax.random.normal(ks[2], (b, hkv, t, hd))
    lens = np.array([t - 3 * i for i in range(b)], np.int32)
    # pack contiguous rows into pool pages (identity layout is fine here;
    # table-steering is covered by the bf16 kernel tests)
    nb = t // bs
    kp = np.zeros((n_pages, bs, hkv, hd), np.float32)
    vp = np.zeros((n_pages, bs, hkv, hd), np.float32)
    tables = np.full((b, nb), n_pages + 3, np.int32)
    nxt = 0
    for row in range(b):
        for i in range(nb):
            tables[row, i] = nxt
            kp[nxt] = np.asarray(k[row, :, i * bs:(i + 1) * bs]).transpose(
                1, 0, 2)
            vp[nxt] = np.asarray(v[row, :, i * bs:(i + 1) * bs]).transpose(
                1, 0, 2)
            nxt += 1
    qk, sk = quantize_kv(jnp.asarray(kp))
    qv, sv = quantize_kv(jnp.asarray(vp))
    return (q, jnp.asarray(kp), jnp.asarray(vp), qk, sk, qv, sv,
            jnp.asarray(tables), jnp.asarray(lens))


@pytest.mark.parametrize("shape,bs", [
    ((2, 6, 2, 32, 32), 8),            # GQA g=3
    ((1, 4, 4, 64, 16), 16),           # MHA, hd=16 (pads to 128)
])
def test_quant_paged_kernel_matches_quant_ref(shape, bs):
    b, hq, hkv, t, hd = shape
    q, _, _, qk, sk, qv, sv, tables, lens = _quant_pool(b, hq, hkv, t, hd,
                                                        bs)
    o = paged_decode_attention_quant(q, qk, qv, sk, sv, tables, lens,
                                     scale=0.2, interpret=True)
    r = paged_decode_attention_quant_ref(q, qk, qv, sk, sv, tables, lens,
                                         scale=0.2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               atol=2e-5, rtol=2e-5)


def test_quant_paged_kernel_tolerance_vs_fp_oracle():
    b, hq, hkv, t, hd, bs = 2, 6, 2, 64, 64, 16
    q, kp, vp, qk, sk, qv, sv, tables, lens = _quant_pool(b, hq, hkv, t,
                                                          hd, bs)
    o = paged_decode_attention_quant(q, qk, qv, sk, sv, tables, lens,
                                     scale=0.2, interpret=True)
    fp = paged_decode_attention_ref(q, kp, vp, tables, lens, scale=0.2)
    # stated decode tolerance of the int8 path vs the exact fp pool: the
    # softmax mix of <=scale/2 per-element dequant error stays well under
    # 5e-2 for unit-normal KV
    err = np.abs(np.asarray(o) - np.asarray(fp)).max()
    assert err < 5e-2, err
    # and the bf16 pool-row kernel over the SAME pages, unquantized, is
    # within bf16 rounding of the fp oracle
    last = lens - 1
    o_fp = paged_decode_attention(
        q.astype(jnp.bfloat16), pool_rows(kp.astype(jnp.bfloat16)),
        pool_rows(vp.astype(jnp.bfloat16)), 0, tables, last, last,
        scale=0.2, kv_heads=hkv, interpret=True)
    np.testing.assert_allclose(np.asarray(o_fp, np.float32), np.asarray(fp),
                               atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------ forward
def test_forward_quantized_paged_tolerance(small_model):
    """Chunked prefill + one decode step through an int8 paged cache stay
    within a stated max-abs logits tolerance of the bf16 paged path."""
    cfg, params = small_model
    b, max_len, bs = 2, 32, 8
    pool = b * (max_len // bs)
    prompts = [[5, 9, 2, 7, 1], [3, 8, 4, 4, 6, 2, 9, 1, 5]]
    tol = 5e-2

    logits = {}
    for kv_dtype in KV_DTYPES:
        pcache = make_paged_cache(cfg, pool, bs, dtype=cfg.cdtype,
                                  kv_dtype=kv_dtype)
        layer0 = next(iter(pcache.values()))["self"]
        assert ("k_scale" in layer0) == (kv_dtype == "int8")
        tables = np.full((b, max_len // bs), pool + 5, np.int32)
        free = list(range(pool))
        outs = []
        for i, p in enumerate(prompts):
            n = -(-len(p) // bs)
            tables[i, :n] = [free.pop(0) for _ in range(n)]
            lg, _, pcache = forward(
                params, jnp.asarray([p]), cfg, cache=pcache,
                cache_index=jnp.zeros((), jnp.int32),
                block_tables=jnp.asarray(tables[i:i + 1]))
            outs.append(np.asarray(lg[0, -1], np.float32))
        lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
        toks = jnp.asarray([[int(o.argmax())] for o in outs], jnp.int32)
        lg, _, _ = forward(params, toks, cfg, cache=pcache, lengths=lengths,
                           block_tables=jnp.asarray(tables))
        logits[kv_dtype] = (outs, np.asarray(lg, np.float32))

    for (pf_b, dec_b), (pf_q, dec_q) in [(logits["bf16"], logits["int8"])]:
        for a, bq in zip(pf_b, pf_q):
            assert np.abs(a - bq).max() < tol
        assert np.abs(dec_b - dec_q).max() < tol


# ------------------------------------------------------------ capacity
def test_default_num_blocks_dtype_aware():
    base = default_num_blocks(4, 64, 16)
    assert base == 16
    # explicit pool wins regardless of dtype
    assert default_num_blocks(4, 64, 16, num_blocks=5, kv_dtype="int8",
                              hd=64) == 5
    # int8 grows the default by payload_bytes*hd/(hd+4)
    got = default_num_blocks(4, 64, 16, kv_dtype="int8", hd=64,
                             payload_bytes=2)
    assert got == int(16 * 128 / 68)
    assert default_num_blocks(4, 64, 16, kv_dtype="int8", hd=16,
                              payload_bytes=4) == int(16 * 64 / 20)
    # no hd -> no byte math possible, stay at base
    assert default_num_blocks(4, 64, 16, kv_dtype="int8") == base


def test_int8_admission_capacity_acceptance(small_model):
    """Acceptance: at the same device byte budget the int8 pool holds
    >= 1.8x the blocks (so admits >= 1.8x the concurrent sequences), and
    serving the same workload at fixed admission uses at most ~half the
    pool."""
    cfg, params = small_model
    sweep = memory_pressure_sweep(
        cfg, params, scenario="summarization", platforms=("GH200",),
        pool_fracs=(1.0,), kv_dtypes=("bf16", "int8"), max_batch=2,
        max_len=32, block_size=4, n_requests=4, seed=0, prompt_cap=12,
        output_cap=6)
    bf16, int8 = sweep["points"]
    assert bf16["kv_dtype"] == "bf16" and int8["kv_dtype"] == "int8"
    # same byte budget, >= 1.8x the block capacity
    assert int8["num_blocks"] * int8["block_bytes"] <= \
        bf16["num_blocks"] * bf16["block_bytes"]
    ratio = int8["num_blocks"] / bf16["num_blocks"]
    assert ratio >= 1.8, ratio
    assert sweep["kv_dtype_deltas"][0]["capacity_ratio"] >= 1.8
    # identical traffic served: at fixed admission the quantized pool
    # runs at <= ~half the utilization
    assert int8["tokens_out"] == bf16["tokens_out"]
    assert int8["peak_pool_utilization"] <= \
        0.56 * bf16["peak_pool_utilization"]


# ------------------------------------------------------------ engine
def test_engine_int8_paged_token_tolerance(small_model):
    """Engine-level: int8 serving completes the same workload with every
    request done; token streams agree with bf16 for this workload (greedy
    argmax is tolerance-stable here) and the default pool is bigger."""
    cfg, params = small_model
    rng = np.random.default_rng(3)
    reqs = lambda: [Request(i, prompt=[int(t) for t in
                                       rng2.integers(1, 100, 8 + 2 * i)],
                            max_new_tokens=5) for i in range(4)]
    outs = {}
    pools = {}
    for dt in KV_DTYPES:
        rng2 = np.random.default_rng(3)
        eng = ServeEngine(cfg, params, max_batch=2, max_len=32,
                          cache="paged", block_size=8, kv_dtype=dt)
        outs[dt] = {r.rid: list(r.generated) for r in eng.run(reqs())}
        pools[dt] = eng.kv.num_blocks
        assert all(len(v) == 5 for v in outs[dt].values())
    assert pools["int8"] / pools["bf16"] >= 1.8
    assert outs["bf16"] == outs["int8"]


def test_engine_rejects_bad_kv_config(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(cfg, params, cache="paged", kv_dtype="fp8")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, share_prefix=True)
    with pytest.raises(ValueError, match="prefix_len"):
        ServeEngine(cfg, params, cache="paged", share_prefix=True,
                    prefix_len=0)
