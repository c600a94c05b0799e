"""Bring-up smoke test: serve smollm-360m at its published widths on a TPU.

    python chip_smoke.py               # one chip, phases 1-7
    python chip_smoke.py --four-chips  # four chips: tp=4 and four replicas

Drives the served path (``ServeEngine`` -> backend -> step bodies and
Pallas kernels) with random weights from seed 0; nothing is downloaded.
Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
There is no CPU path: the script exits non-zero when JAX finds no TPU, and
at the first phase that fails.  Times are facts of this run, split into
set-up (tracing, planning, compilation) and run; they are not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
ARCH = "smollm-360m"
N_REQUESTS, MAX_NEW, MAX_BATCH, MAX_LEN = 8, 32, 8, 2048
# prompt lengths drawn from seed 0 in steps of 128: a 256-token prefill
# chunk then leaves chunks of only two lengths (one compile each)
PROMPT_LENS = tuple(range(128, 1025, 128))
PAGED = dict(cache="paged", block_size=16, prefill_chunk=256)
FUSED_LAYERS = 2          # planning all 32 layers eqn by eqn takes ~13 min
TP_ARCH, TP = "codeqwen1.5-7b", 4
TP_CUT_LAYERS = 4
# bf16 logits against a reference: 8 bf16 ulps at the logits' magnitude
LOGIT_ULPS = 8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_phase(need: int) -> dict:
    """Phase 1: a TPU or nothing; then the compile cache, before any
    compile.  Imports the repo only once a TPU is known to be there."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
                 f"{devs[0].platform} device(s)")
    if len(devs) < need:
        sys.exit(f"chip_smoke: needs {need} TPU devices; JAX found "
                 f"{len(devs)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit("device", **info, jax=jax.__version__, compile_cache=cache_dir)
    return info


def make_requests(cfg, n: int, lengths, max_new: int):
    """Requests built the way ``repro.launch.serve`` builds them: seeded
    random prompts, a fixed number of new tokens each."""
    from repro.inference.engine import Request
    rng = np.random.default_rng(SEED)
    plens = rng.choice(np.asarray(lengths), size=n)
    return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, int(p))),
                    max_new_tokens=max_new)
            for i, p in enumerate(plens)]


def serve(cfg, params, make, **engine_kwargs):
    """One engine, two passes over the same requests: the first pays
    tracing/planning/compiles (set-up), the second is the run whose
    tokens are returned.  Every request must finish with in-vocab
    tokens."""
    from repro.inference.engine import ServeEngine
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, **engine_kwargs)
    eng.run(make())
    setup = time.perf_counter() - t0
    eng.reset()
    t1 = time.perf_counter()
    done = eng.run(make())
    run = time.perf_counter() - t1
    status = [r.status for r in done]
    check(len(done) > 0 and all(s == "done" for s in status),
          f"not every request finished: {status}")
    tokens = {r.rid: [int(t) for t in r.generated] for r in done}
    for r in done:
        toks = tokens[r.rid]
        check(len(toks) == r.max_new_tokens,
              f"request {r.rid} emitted {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"request {r.rid} emitted a token outside the vocab")
    return eng, tokens, {"setup_s": setup, "run_s": run}


def agreement(ref: dict, got: dict) -> dict:
    """Identical requests, and the first differing step of the others."""
    first = {}
    for rid, a in ref.items():
        b = got[rid]
        if a != b:
            first[rid] = next(i for i, (x, y) in enumerate(zip(a, b))
                              if x != y)
    return {"requests_identical": len(ref) - len(first),
            "requests": len(ref), "first_differing_step": first}


def logit_tol(ref) -> float:
    import jax.numpy as jnp
    return LOGIT_ULPS * float(jnp.finfo(jnp.bfloat16).eps) * float(
        np.max(np.abs(ref)))


def prefill_logits(eng, prompt) -> np.ndarray:
    """Last-position logits of one prompt through the engine's own
    contiguous prefill step (the served step body, compiled once per
    prompt length)."""
    import jax.numpy as jnp
    plen = len(prompt)
    toks = np.zeros((1, eng._bucket(plen)), np.int32)
    toks[0, :plen] = prompt
    logits, _ = eng.backend.prefill(eng.cache, jnp.asarray(toks), 0, plen)
    return np.asarray(logits[0], np.float32)


def margins_at_difference(eng, requests, ref: dict, got: dict) -> dict:
    """For each request whose tokens differ: at the first differing step,
    the reference engine's logit of its own token minus its logit of the
    other run's token, from a contiguous prefill of prompt + the shared
    prefix.  A margin near the bf16 noise of the logits marks a near-tie
    that rounding can flip; a large one marks a real divergence."""
    out = {}
    for rid, step in agreement(ref, got)["first_differing_step"].items():
        a, b = ref[rid][step], got[rid][step]
        logits = prefill_logits(eng, list(requests[rid].prompt)
                                + ref[rid][:step])
        out[rid] = {"step": step, "ref_token": a, "other_token": b,
                    "logit_margin": float(logits[a] - logits[b])}
    return out


def describe(cfg) -> dict:
    return {"arch": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
            "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.hd, "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
            "dtype": cfg.param_dtype}


def init_on_device(cfg, device):
    """``init_params(seed 0)`` compiled and placed on one device."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import init_params
    key = jax.random.PRNGKey(SEED)
    return jax.jit(lambda: init_params(key, cfg),
                   out_shardings=SingleDeviceSharding(device))()


# --------------------------------------------------------------- one chip
def kernel_phase(cfg, interpret: bool = False) -> dict:
    """Phase 6: the bf16 pool-row and int8 paged decode kernels at the
    model's widths against ``kernels/decode_attention/ref.py``."""
    import jax
    import jax.numpy as jnp
    from repro.inference.kv_quant import quantize_kv
    from repro.kernels.decode_attention.ops import (
        paged_decode_attention, paged_decode_attention_quant)
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_quant_ref, paged_decode_attention_ref,
        pool_rows)
    b, pages, bs = MAX_BATCH, 512, PAGED["block_size"]
    nb = MAX_LEN // bs
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (b, hq, hd)).astype(jnp.bfloat16)
    kp = jax.random.normal(kk, (pages, bs, hkv, hd)).astype(jnp.bfloat16)
    vp = jax.random.normal(kv, (pages, bs, hkv, hd)).astype(jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    tables = jnp.asarray(rng.integers(0, pages, (b, nb)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, nb * bs + 1, b), jnp.int32)
    scale = hd ** -0.5
    out = {}
    o = paged_decode_attention(q, pool_rows(kp), pool_rows(vp), 0, tables,
                               lens - 1, lens - 1, scale=scale,
                               kv_heads=hkv, interpret=interpret)
    r = paged_decode_attention_ref(q, kp, vp, tables, lens, scale=scale)
    out["bf16"] = (o, r)
    qk, sk = quantize_kv(kp)
    qv, sv = quantize_kv(vp)
    o = paged_decode_attention_quant(q, qk, qv, sk, sv, tables, lens,
                                     scale=scale, interpret=interpret)
    r = paged_decode_attention_quant_ref(q, qk, qv, sk, sv, tables, lens,
                                         scale=scale)
    out["int8"] = (o, r)
    fields = {}
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    for name, (o, r) in out.items():
        o = np.asarray(o, np.float32)
        r = np.asarray(r, np.float32)
        err = float(np.max(np.abs(o - r)))
        tol = 2 * eps * float(np.max(np.abs(r)))
        check(np.all(np.isfinite(o)), f"{name} kernel output not finite")
        check(err <= tol, f"{name} kernel max abs err {err} > {tol}")
        fields[name] = {"max_abs_err": err, "tol": tol}
    return fields


def one_chip() -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.inference.speculative import draft_params_from_target
    from repro.models import forward

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_on_device(cfg, jax.devices()[0])
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    def make():
        return make_requests(cfg, N_REQUESTS, PROMPT_LENS, MAX_NEW)

    plens = [len(r.prompt) for r in make()]
    base = dict(max_batch=MAX_BATCH, max_len=MAX_LEN)

    # 2 -- contiguous cache, plan jit
    eng2, tok2, t = serve(cfg, params, make, **base)
    emit("contiguous_jit", **describe(cfg), requests_done=len(tok2),
         prompt_lens=plens, new_tokens=MAX_NEW, init_params_setup_s=init_s,
         **t)

    # 3 -- paged bf16
    _, tok3, t = serve(cfg, params, make, **base, **PAGED)
    emit("paged_bf16", requests_done=len(tok3), **PAGED,
         vs_contiguous=agreement(tok2, tok3),
         margins_at_first_difference=margins_at_difference(
             eng2, make(), tok2, tok3), **t)

    # 4 -- paged int8 + copy-on-write prefix sharing
    eng4, tok4, t = serve(cfg, params, make, **base, **PAGED,
                          kv_dtype="int8", share_prefix=True)
    emit("paged_int8_share_prefix", requests_done=len(tok4),
         prefix_adoptions=eng4.stats.prefix_adoptions,
         vs_contiguous=agreement(tok2, tok4), **t)
    del eng4

    # 5 -- launch-plan runtime with fused Pallas kernels, depth cut
    cut = cfg.replace(n_layers=FUSED_LAYERS)
    cut_params = draft_params_from_target(params, cut)
    _, tok_jit, _ = serve(cut, cut_params, make, **base, **PAGED)
    eng5, tok5, t = serve(cut, cut_params, make, **base, **PAGED,
                          plan="fused")
    hits = dict(eng5.stats.rule_hits)
    check(bool(hits), "plan fused substituted no fused kernel")
    emit("plan_fused", layers=FUSED_LAYERS,
         depth_cut=f"{cfg.n_layers} -> {FUSED_LAYERS} layers, widths kept",
         compared_with=f"plan jit at {FUSED_LAYERS} layers, same cache",
         requests_done=len(tok5), rule_hits=hits,
         dispatches_per_decode_step=eng5.stats.dispatches_per_decode_step,
         vs_jit=agreement(tok_jit, tok5), **t)
    del eng5

    # 6 -- paged decode kernels, compiled
    t0 = time.perf_counter()
    fields = kernel_phase(cfg)
    emit("paged_decode_kernels", interpret=False, batch=MAX_BATCH,
         pages=512, block_size=PAGED["block_size"], **fields,
         tol_reason="2 bf16 ulps at the output's magnitude: kernel and "
                    "reference compute in f32 from the same inputs and "
                    "round once to bf16",
         setup_and_run_s=time.perf_counter() - t0)

    # 7 -- served prefill logits against a float32 reference
    prompt = make()[0].prompt
    t0 = time.perf_counter()
    served = prefill_logits(eng2, prompt)
    cfg32 = cfg.replace(param_dtype="float32", compute_dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: forward(p, x, cfg32)[0][0, -1])(
            p32, jnp.asarray([prompt], jnp.int32))
    ref = np.asarray(ref, np.float32)
    diff = np.abs(served - ref)
    tol = logit_tol(ref)
    check(np.all(np.isfinite(served)), "served logits not finite")
    check(float(diff.max()) <= tol,
          f"served logits differ from the f32 reference by "
          f"{float(diff.max())} > {tol}")
    emit("reference", prompt_len=len(prompt),
         max_abs_diff=float(diff.max()), mean_abs_diff=float(diff.mean()),
         tol=tol, max_abs_ref=float(np.max(np.abs(ref))),
         argmax_served=int(served.argmax()), argmax_ref=int(ref.argmax()),
         tol_reason=f"{LOGIT_ULPS} bf16 ulps at the logits' magnitude: the "
                    "served path keeps weights, activations and KV in bf16 "
                    "against an f32 forward at highest matmul precision",
         setup_and_run_s=time.perf_counter() - t0)


# ------------------------------------------------------------- four chips
def tp_phase() -> None:
    """codeqwen1.5-7b (about 16.4 GB of bf16 params, more than one chip
    holds) at tp=4, then its 4-layer cut at tp=4 against tp=1."""
    import jax
    from repro.configs import get_config
    from repro.inference.backends.sharded import init_sharded_params

    cfg = get_config(TP_ARCH)
    key = jax.random.PRNGKey(SEED)

    def make_for(c):
        return lambda: make_requests(c, 4, (256, 384, 512), 16)

    base = dict(max_batch=4, max_len=1024)
    t0 = time.perf_counter()
    params = init_sharded_params(key, cfg, TP)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:TP]]
    eng, tok, t = serve(cfg, params, make_for(cfg), tp=TP, **base)
    emit("tp4_full", **describe(cfg), tp=TP, requests_done=len(tok),
         param_bytes=sum(a.nbytes for a in jax.tree.leaves(params)),
         bytes_in_use_per_device_after_init=held,
         init_params_setup_s=init_s, **t)
    del eng, params
    gc.collect()                  # free the 7B model before the next ones

    cut = cfg.replace(n_layers=TP_CUT_LAYERS)
    make = make_for(cut)
    e4, tok4, t4 = serve(cut, init_sharded_params(key, cut, TP), make,
                         tp=TP, **base)
    e1, tok1, t1 = serve(cut, init_on_device(cut, jax.devices()[0]), make,
                         **base)
    prompt = make()[0].prompt
    l4, l1 = prefill_logits(e4, prompt), prefill_logits(e1, prompt)
    diff = float(np.max(np.abs(l4 - l1)))
    tol = logit_tol(l1)
    check(diff <= tol, f"tp={TP} logits differ from tp=1 by {diff} > {tol}")
    emit("tp4_vs_tp1", layers=TP_CUT_LAYERS,
         depth_cut=f"{cfg.n_layers} -> {TP_CUT_LAYERS} layers, widths kept",
         tokens=agreement(tok1, tok4), max_logit_diff=diff, tol=tol,
         prompt_len=len(prompt), setup_s={"tp4": t4["setup_s"],
                                          "tp1": t1["setup_s"]})


def replica_phase() -> None:
    """Four one-chip smollm-360m replicas behind the router against one
    engine serving the same requests."""
    import jax
    from repro.configs import get_config
    from repro.inference.engine import ServeEngine
    from repro.inference.fleet import ReplicaFleet
    from repro.inference.router import RequestRouter

    cfg = get_config(ARCH)
    params = init_on_device(cfg, jax.devices()[0])

    def make():
        return make_requests(cfg, 16, PROMPT_LENS, MAX_NEW)

    kw = dict(max_batch=4, max_len=MAX_LEN, **PAGED)
    t0 = time.perf_counter()
    fleet = ReplicaFleet(cfg, params, replicas=4, **kw)
    reps = fleet.live()
    devices = [rep.engine.backend.info.devices[0] for rep in reps]
    held = [sorted({d.id for a in jax.tree.leaves(
        (rep.engine.backend.params, rep.engine.cache)) for d in a.devices()})
        for rep in reps]
    check(len(set(devices)) == 4, f"replicas share devices: {devices}")
    check(held == [[d] for d in devices],
          f"replica params/caches not on their own device: {held}")
    report = RequestRouter(fleet, policy="least-queue-depth").route(make())
    fleet_s = time.perf_counter() - t0
    got = report.tokens_by_rid
    check(len(got) == 16, f"fleet finished {len(got)} of 16 requests")
    t0 = time.perf_counter()
    done = ServeEngine(cfg, params, **kw).run(make())
    single_s = time.perf_counter() - t0
    ref = {r.rid: [int(t) for t in r.generated] for r in done}
    got = {rid: [int(t) for t in toks] for rid, toks in got.items()}
    per_replica = {str(r): sum(1 for v in report.assignment.values()
                               if v == r) for r in range(4)}
    emit("four_replicas", replica_devices=devices, arrays_on=held,
         requests_per_replica=per_replica, requests_done=len(got),
         vs_single_engine=agreement(ref, got),
         fleet_setup_and_run_s=fleet_s, single_setup_and_run_s=single_s)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip checks: tp=4 serving of "
                         f"{TP_ARCH} and four one-chip replicas")
    args = ap.parse_args()
    info = device_phase(need=4 if args.four_chips else 1)
    if args.four_chips:
        tp_phase()
        replica_phase()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
