"""Serving launcher: continuous-batching engine over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --requests 8 --max-batch 4

Pick an execution plan with ``--plan``: the default ``jit`` serves via
whole-step jax.jit closures; ``eager`` / ``chain`` / ``auto`` /
``whole_graph`` / ``fused`` route prefill/decode through the launch-plan
runtime and report real per-step dispatch counts plus modeled TKLQT for
``--platform``.  ``--plan autotuned --plan-table plan_table.json`` loads
the measured winners written by ``repro.launch.autotune``.

Pick a KV cache with ``--cache``: ``paged`` serves through the
block-table paged allocator (``--block-size`` tokens per block,
``--num-blocks`` pool size, ``--prefill-chunk`` chunked prefill), with
``--offload host`` staging evicted blocks in host memory priced by
``--platform``'s coupling link; the JSON report then carries block-pool
utilization, preemption, and offload-traffic counters.

Pick a tensor-parallel degree with ``--tp``: ``--tp N`` serves through
the sharded backend (params/KV head-sharded over an N-way model mesh,
shard_map prefill/decode with psum'd partial outputs) and the JSON
report carries per-device dispatch counts plus collective-payload
counters priced over ``--platform``'s coupling link.  Needs N visible
devices — on CPU set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Turn on speculative decoding with ``--speculative``: a truncated-target
draft (``--draft-layers`` superblocks, default half) proposes
``--spec-k`` tokens per round and the target verifies them in one
batched forward — emitted tokens stay byte-identical to greedy, and the
JSON report carries accept-rate / steps-per-emitted-token / draft
dispatch-stream counters priced by ``--platform``.  ``--spec-inflection``
feeds the measured CPU->GPU-bound inflection batch to the depth policy
(deep while dispatch-bound, off past the inflection).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core.device_model import PLATFORMS
from repro.core.export import save_request_trace
from repro.core.fusion import json_sanitize
from repro.inference.engine import (CACHE_MODES, OFFLOAD_MODES,
                                    PLAN_STRATEGIES, Request, ServeEngine)
from repro.inference.kv_quant import KV_DTYPES
from repro.configs import get_config, reduced
from repro.models import init_params
from repro.telemetry.critical_path import (SLO, analyze, record_goodput,
                                           triage)
from repro.telemetry.tracing import RequestTracer
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--plan", default="jit", choices=PLAN_STRATEGIES)
    ap.add_argument("--plan-table", default=None,
                    help="plan_table.json from repro.launch.autotune "
                         "(required with --plan autotuned)")
    ap.add_argument("--platform", default="TPU-v5e",
                    choices=sorted(PLATFORMS))
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: 1 = single-device "
                         "LocalBackend, N>1 = sharded backend over an "
                         "N-way model mesh")
    ap.add_argument("--cache", default="contiguous", choices=CACHE_MODES)
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged cache)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="block-pool size; default fits every slot at "
                         "--max-len (no memory pressure)")
    ap.add_argument("--kv-dtype", default="bf16", choices=KV_DTYPES,
                    help="paged KV storage dtype: int8 quantizes pages "
                         "per-(token, head) with f32 scales (entry cost "
                         "hd+4 bytes vs 2*hd) and dequantizes at load; "
                         "the default pool sizes up by the byte ratio")
    ap.add_argument("--share-prefix", action="store_true",
                    help="copy-on-write prefix sharing: requests whose "
                         "prompts share a verified token prefix map their "
                         "leading full blocks to the same pool pages "
                         "(paged cache only)")
    ap.add_argument("--offload", default="none", choices=OFFLOAD_MODES,
                    help="host: evict cold blocks to host memory and "
                         "restore on resume; none: preempt + recompute")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admit prompts in chunks of this many tokens, "
                         "interleaved with decode steps")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the warmup pass; measured fields (launch "
                         "tax, TTFT, ITL) then include jit-compile time")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-propose / batched-verify decoding "
                         "(greedy-lossless; needs --plan jit)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per round (>= 1)")
    ap.add_argument("--spec-inflection", type=int, default=None,
                    help="measured CPU->GPU-bound inflection batch for "
                         "the launch-tax-aware depth policy (from "
                         "launch.characterize); default: always deep")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="superblocks in the truncated-target draft "
                         "(default: half the target's)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine's MetricsRegistry here after "
                         "the measured run: Prometheus text exposition "
                         "when the path ends in .prom, else a JSON "
                         "snapshot")
    ap.add_argument("--trace-out", default=None,
                    help="write the per-request critical-path trace "
                         "(Perfetto/chrome JSON, one track per request)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT SLO in ms for goodput accounting "
                         "(0 disables; unset = no TTFT bound)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="mean-ITL SLO in ms for goodput accounting "
                         "(0 disables; unset = no ITL bound)")
    ap.add_argument("--attribution", action="store_true",
                    help="include the per-operator launch/queue/exec "
                         "attribution of one decode step plus the live "
                         "boundedness verdict in the report (needs a "
                         "launch-plan mode, not --plan jit)")
    args = ap.parse_args()
    use_compile_cache()
    if args.attribution and args.plan == "jit":
        ap.error("--attribution needs a launch-plan mode (--plan eager/"
                 "chain/auto/whole_graph/fused): plan=jit dispatches one "
                 "whole-step executable with no kernel-level provenance "
                 "to attribute")

    if args.cache != "paged" and (args.kv_dtype != "bf16"
                                  or args.share_prefix):
        ap.error("--kv-dtype/--share-prefix need --cache paged (the "
                 "contiguous cache has no block pool to quantize or share)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    draft_cfg = None
    if args.speculative:
        # actionable CLI validation before any params materialize
        if args.plan != "jit":
            ap.error(f"--speculative needs --plan jit, got {args.plan} "
                     "(the launch-plan runtime replays fixed single-token "
                     "streams; model the draft/verify trade with "
                     "launch.characterize --spec-sweep instead)")
        if args.spec_k < 1:
            ap.error(f"--spec-k must be >= 1, got {args.spec_k} "
                     "(drop --speculative to serve without a draft)")
        from repro.inference.speculative import (default_draft_config,
                                                 validate_draft)
        if args.draft_layers is not None:
            if not 1 <= args.draft_layers <= cfg.n_superblocks:
                ap.error(f"--draft-layers must be in [1, "
                         f"{cfg.n_superblocks}] for {cfg.name} "
                         f"({cfg.n_superblocks} superblocks), got "
                         f"{args.draft_layers}")
            draft_cfg = cfg.replace(
                name=f"{cfg.name}-draft{args.draft_layers}sb",
                n_layers=args.draft_layers * len(cfg.block_pattern))
        else:
            draft_cfg = default_draft_config(cfg)
        try:
            validate_draft(cfg, draft_cfg, args.spec_k)
        except ValueError as e:
            ap.error(str(e))
    if args.tp > 1:
        # straight into the tp layout: a model that only fits across the
        # mesh never sits whole on one device
        from repro.inference.backends.sharded import init_sharded_params
        params = init_sharded_params(jax.random.PRNGKey(0), cfg, args.tp)
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
    tracer = RequestTracer()
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      tracer=tracer,
                      max_len=args.max_len, plan=args.plan,
                      platform=args.platform, plan_table=args.plan_table,
                      tp=args.tp,
                      cache=args.cache, block_size=args.block_size,
                      num_blocks=args.num_blocks, offload=args.offload,
                      kv_dtype=args.kv_dtype,
                      share_prefix=args.share_prefix,
                      prefill_chunk=args.prefill_chunk,
                      speculative=args.speculative, draft_config=draft_cfg,
                      spec_k=args.spec_k,
                      spec_inflection=args.spec_inflection)

    def make_requests():
        rng = np.random.default_rng(0)
        return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, 12)),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]

    if not args.no_warmup:
        # pay tracing/planning/jit before measuring: the reported launch
        # tax and TTFT/ITL are steady-state serving, not compile time
        eng.run(make_requests())
        eng.reset()
        # reset() keeps the (shareable) tracer; drop warmup lifecycles so
        # the triage decomposition covers the measured run only
        tracer.clear()
    reqs = make_requests()
    t0 = time.time()
    done = eng.run(reqs)
    dt = time.time() - t0
    st = eng.stats
    occ = st.slot_occupancy
    report = {
        "arch": cfg.name,
        "requests": sum(1 for r in done if r.status == "done"),
        "plan": st.plan,
        "cache": args.cache,
        "slot_occupancy": {
            "mean": round(float(np.mean(occ)), 2) if occ else 0.0,
            "peak": int(max(occ)) if occ else 0,
        },
        "block_pool_utilization": {
            "mean": round(st.mean_block_pool_utilization, 3),
            "peak": round(st.peak_block_pool_utilization, 3),
        },
        "kv_dtype": args.kv_dtype,
        "share_prefix": args.share_prefix,
        "num_blocks": (eng.kv.num_blocks
                       if args.cache == "paged" else 0),
        "prefix_adoptions": st.prefix_adoptions,
        "shared_prefix_tokens": st.shared_prefix_tokens,
        "kv_cow_copies": (eng.kv.pool.cow_copies_total
                          if args.cache == "paged" else 0),
        "preemptions": st.preemptions,
        "rejected": st.rejected,
        "prefill_chunks": st.prefill_chunks,
        "offload_bytes": st.offload_bytes,
        "restore_bytes": st.restore_bytes,
        "modeled_offload_tax_us": round(st.modeled_offload_tax_s * 1e6, 1),
        "tokens_out": st.tokens_out,
        "decode_steps": st.decode_steps,
        "decode_dispatches": st.decode_dispatches,
        "tp": st.tp,
        "per_device_dispatches": {str(d): n for d, n in
                                  sorted(st.per_device_dispatches.items())},
        "collectives": st.collectives,
        "collective_bytes": st.collective_bytes,
        "collective_bytes_per_decode_step": round(
            st.collective_bytes_per_decode_step, 1),
        "modeled_collective_tax_us": round(
            st.modeled_collective_tax_s * 1e6, 1),
        "dispatches_per_decode_step": round(
            st.dispatches_per_decode_step, 2),
        "fused_dispatches_per_decode_step": round(
            st.fused_dispatches_per_decode_step, 2),
        "rule_hits": dict(st.rule_hits),
        "prefill_dispatches": st.prefill_dispatches,
        "modeled_tklqt_us": round(st.modeled_tklqt_s * 1e6, 1),
        "measured_launch_tax_per_step_us": round(
            st.launch_tax_per_step_s * 1e6, 1),
        "mean_occupancy": round(float(np.mean(occ)), 2) if occ else 0.0,
        "tok_per_s": round(st.tokens_out / dt, 1),
        "ttft_ms": {rid: round(t * 1e3, 3)
                    for rid, t in sorted(st.ttft_s.items())},
        "mean_ttft_ms": round(st.mean_ttft_s * 1e3, 3),
        "mean_itl_ms": round(st.mean_itl_s * 1e3, 3),
        "speculative": args.speculative,
        "spec_k": args.spec_k if args.speculative else 0,
        "draft": draft_cfg.name if draft_cfg is not None else None,
        "spec_rounds": st.spec_rounds,
        "proposed": st.proposed,
        "accepted": st.accepted,
        "corrections": st.corrections,
        "accept_rate": round(st.accept_rate, 3),
        "steps_per_emitted_token": round(st.steps_per_emitted_token, 3),
        "draft_dispatches": st.draft_dispatches,
        "modeled_draft_launch_tax_us": round(
            st.modeled_draft_launch_tax_s * 1e6, 1),
    }
    if args.attribution:
        pd = eng._planned_decode
        rep = pd.attribution if pd is not None else None
        report["attribution"] = None if rep is None else {
            "complete": rep.complete,
            "total_events": rep.total_events,
            "accounted_launches": float(rep.accounted_launches),
            "tklqt_us": round(rep.tklqt_s * 1e6, 3),
            "rows": rep.as_dicts(),
        }
        report["boundedness"] = (eng.monitor.summary()
                                 if eng.monitor is not None else None)
    # critical-path decomposition + goodput BEFORE the registry export,
    # so --metrics-out snapshots carry the goodput families
    slo = SLO.resolve(None, args.slo_ttft_ms, args.slo_itl_ms)
    analysis = analyze(tracer)
    tri = triage(analysis, slo)
    if "slo_report" in tri:
        record_goodput(eng.registry, tri["slo_report"])
    report["triage"] = tri
    if args.trace_out:
        save_request_trace(
            analysis, args.trace_out, platform=args.platform,
            host_spans=(eng.telemetry.spans
                        if eng.telemetry is not None else ()))
        report["trace_out"] = args.trace_out
    if args.metrics_out:
        if args.metrics_out.endswith(".prom"):
            with open(args.metrics_out, "w") as fh:
                fh.write(eng.registry.to_prometheus())
        else:
            with open(args.metrics_out, "w") as fh:
                json.dump(json_sanitize(eng.registry.snapshot()), fh,
                          indent=2, allow_nan=False)
        report["metrics_out"] = args.metrics_out
    # strict JSON even when a measured field degenerates to inf/nan —
    # the same json_safe leaf conversion the bench artifacts use
    print(json.dumps(json_sanitize(report), allow_nan=False))


if __name__ == "__main__":
    main()
