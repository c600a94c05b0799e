"""Paged KV decode path: ops-level paged vs contiguous decode-attention
latency (Pallas compiled on a TPU, interpreted elsewhere), engine-level
paged vs contiguous decode steps, and the measured offload traffic +
link-priced tax of a pool-constrained run — the capacity half of the
serving story."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, csv_row
from repro.configs import get_config, reduced
from repro.core.device_model import PLATFORMS, offload_cost_s
from repro.inference.engine import Request, ServeEngine
from repro.inference.kv_quant import quantize_kv
from repro.kernels import pallas_interpret
from repro.kernels.decode_attention.ops import (
    decode_attention, paged_decode_attention, paged_decode_attention_quant)
from repro.kernels.decode_attention.ref import pool_rows
from repro.models import init_params

ARCH = "smollm-360m"
REPEATS = 3 if FAST else 5
MAX_LEN = 64
BLOCK = 8


def _time(fn, repeats=REPEATS):
    jax.block_until_ready(fn())        # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / repeats


def _requests(cfg, n):
    rng = np.random.default_rng(0)
    return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, 12)),
                    max_new_tokens=8) for i in range(n)]


def _serve(cfg, params, **kw):
    eng = ServeEngine(cfg, params, max_batch=2, max_len=MAX_LEN, **kw)
    eng.run(_requests(cfg, 6))         # warmup: pay jit once
    eng.reset()
    eng.run(_requests(cfg, 6))
    return eng.stats


def run() -> list[str]:
    rows = []
    # ---- ops level: one decode-attention call, contiguous vs block-table
    B, HQ, HKV, hd, bs, nb = 2, 4, 2, 64, 64, 4
    t = bs * nb
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, HQ, hd))
    k = jax.random.normal(ks[1], (B, HKV, t, hd))
    v = jax.random.normal(ks[2], (B, HKV, t, hd))
    # identity page layout: page b*nb+i holds row b's tokens [i*bs,(i+1)*bs)
    kp = k.transpose(0, 2, 1, 3).reshape(B * nb, bs, HKV, hd)
    vp = v.transpose(0, 2, 1, 3).reshape(B * nb, bs, HKV, hd)
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    lens = jnp.full((B,), t, jnp.int32)
    interp = pallas_interpret()
    tc = _time(lambda: decode_attention(q, k, v, t, scale=0.2, block_kv=bs,
                                        interpret=interp))
    # the served pool's rows: one bf16 layer, read in place by the kernel
    qb = q.astype(jnp.bfloat16)
    kr, vr = (pool_rows(x.astype(jnp.bfloat16)) for x in (kp, vp))
    tp = _time(lambda: paged_decode_attention(
        qb, kr, vr, 0, tables, lens - 1, lens - 1, scale=0.2, kv_heads=HKV,
        interpret=interp))
    rows.append(csv_row("paged_decode/ops_contiguous", tc * 1e6,
                        f"B={B};T={t};block_kv={bs}"))
    rows.append(csv_row("paged_decode/ops_paged", tp * 1e6,
                        f"B={B};pages={B * nb};bs={bs};"
                        f"vs_contig={tp / tc:.2f}x"))
    # quantized pool: int8 payloads + per-(token, head) f32 scales,
    # dequantized inside the kernel after each page DMA
    qk, sk = quantize_kv(kp)
    qv, sv = quantize_kv(vp)
    tq = _time(lambda: paged_decode_attention_quant(
        q, qk, qv, sk, sv, tables, lens, scale=0.2, interpret=interp))
    rows.append(csv_row("paged_decode/ops_paged_int8", tq * 1e6,
                        f"B={B};pages={B * nb};bs={bs};"
                        f"vs_paged_bf16={tq / tp:.2f}x"))

    # ---- engine level: decode steps through each cache, same traffic
    cfg = reduced(get_config(ARCH), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    st_c = _serve(cfg, params)
    st_p = _serve(cfg, params, cache="paged", block_size=BLOCK)
    st_q = _serve(cfg, params, cache="paged", block_size=BLOCK,
                  kv_dtype="int8")
    for name, st in (("engine_contiguous", st_c), ("engine_paged", st_p),
                     ("engine_paged_int8", st_q)):
        steps = st.step_times_s
        mean_step = sum(steps) / len(steps) if steps else 0.0
        rows.append(csv_row(
            f"paged_decode/{name}", mean_step * 1e6,
            f"decode_steps={st.decode_steps};tokens={st.tokens_out}"))

    # ---- pool pressure: measured offload traffic, link-priced LC vs CC
    # (same per-block transfer count the engine itself prices with, so
    # these rows agree with serve/characterize for identical traffic)
    st_o = _serve(cfg, params, cache="paged", block_size=4, num_blocks=8,
                  offload="host", prefill_chunk=8)
    for plat in ("Intel+H100", "GH200"):
        spec = PLATFORMS[plat]
        tax = offload_cost_s(spec, st_o.offload_bytes + st_o.restore_bytes,
                             transfers=max(st_o.offload_transfers, 1))
        rows.append(csv_row(
            f"paged_decode/offload_tax_{spec.coupling}", 0.0,
            f"platform={plat};preemptions={st_o.preemptions};"
            f"offload_bytes={st_o.offload_bytes};"
            f"transfers={st_o.offload_transfers};"
            f"modeled_tax_us={tax * 1e6:.1f}"))
    return rows
