"""Measured serving characterization launcher: scenario x batch x plan sweep.

    PYTHONPATH=src python -m repro.launch.characterize --arch smollm-360m \
        --reduced --scenario chatbot --batches 1,2,4 --plan auto

Drives the live ServeEngine with a named traffic scenario (see
``repro.workload.list_scenarios``), records host telemetry, prints
per-batch measured launch tax + TTFT/ITL percentiles with a
CPU/GPU-bound classification, and writes to ``--out-dir``:

  workload_<scenario>.jsonl     replayable traffic trace (--replay loads one)
  trace_<scenario>_b<N>.json    merged host+modeled-device Chrome trace
                                (open in Perfetto / chrome://tracing)
  characterize.json             BENCH-style summary of the whole sweep

``--memory-sweep`` runs the paged-KV memory-pressure sweep instead:
the same seeded traffic is served with the block pool driven past
capacity on each ``--sweep-platforms`` device model (LC/PCIe vs
CC/NVLink-C2C), printing measured offload traffic and the link-priced
offload tax per architecture, and writing ``memory_sweep.json``.

``--tp-sweep`` models the tensor-parallel launch story instead: the
decode kernel stream is traced once per batch, then priced per
(platform, tp) with per-device dispatch streams (launch tax x tp),
1/tp device work, and per-layer psum payloads over each platform's
coupling link — printing how the CPU->GPU-bound inflection batch moves
with tp on LC vs CC parts, and writing ``tp_sweep.json``.

``--spec-sweep`` runs the speculative-decoding depth sweep: the live
engine measures acceptance and steps-per-emitted-token per (k, batch),
then the target/draft decode streams are priced per platform with the
draft's serialized dispatch stream and the (k+1)x verify work —
printing the LC-vs-CC winning batch regions (speculation pays where
decode is dispatch-bound; CC's region is wider) and writing
``spec_sweep.json``.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs import get_config, reduced
from repro.core.device_model import PLATFORMS
from repro.core.export import save_merged_trace
from repro.inference.engine import PLAN_STRATEGIES
from repro.models import init_params
from repro.telemetry.characterize import (characterize,
                                          memory_pressure_sweep, spec_sweep,
                                          tp_sweep)
from repro.workload import list_scenarios, load_workload, save_workload
from repro.launch.compile_cache import use_compile_cache


def write_artifacts(result, out_dir: str) -> dict:
    """Write workload JSONL, per-batch Chrome traces, and the summary."""
    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    wl = os.path.join(out_dir, f"workload_{result.scenario}.jsonl")
    paths["workload"] = save_workload(result.workload, wl)
    for p in result.points:
        tr = os.path.join(out_dir,
                          f"trace_{result.scenario}_b{p.batch}.json")
        paths[f"trace_b{p.batch}"] = save_merged_trace(
            p.spans, result.platform, tr,
            device_events=p.modeled_events,
            device_anchors=p.decode_anchors,
            metadata={"arch": result.arch, "scenario": result.scenario,
                      "plan": result.plan, "batch": p.batch})
    summary = os.path.join(out_dir, "characterize.json")
    with open(summary, "w") as f:
        json.dump(result.summary(), f, indent=2)
    paths["summary"] = summary
    return paths


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scenario", default="chatbot",
                    choices=list_scenarios())
    ap.add_argument("--batches", default="1,2,4",
                    help="comma-separated slot-pool sizes to sweep")
    ap.add_argument("--plan", default="auto", choices=PLAN_STRATEGIES)
    ap.add_argument("--platform", default="TPU-v5e",
                    choices=sorted(PLATFORMS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-cap", type=int, default=24,
                    help="clip scenario prompt lengths (0 = no cap)")
    ap.add_argument("--output-cap", type=int, default=8,
                    help="clip scenario output lengths (0 = no cap)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="compress the arrival timeline by this factor")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the warmup pass (timings include compiles)")
    ap.add_argument("--replay", default=None,
                    help="replay a recorded workload JSONL instead of "
                         "generating from the scenario")
    ap.add_argument("--out-dir", default="characterize-out")
    ap.add_argument("--memory-sweep", action="store_true",
                    help="run the paged-KV memory-pressure sweep (LC vs "
                         "CC offload tax) instead of the batch sweep")
    ap.add_argument("--sweep-platforms", default="Intel+H100,GH200",
                    help="comma-separated device models for "
                         "--memory-sweep / --tp-sweep")
    ap.add_argument("--pool-fracs", default="1.0,0.5,0.33",
                    help="pool sizes as fractions of the no-pressure pool")
    ap.add_argument("--block-size", type=int, default=4,
                    help="tokens per KV block for --memory-sweep")
    ap.add_argument("--kv-dtypes", default="bf16",
                    help="comma-separated paged-KV storage dtypes for "
                         "--memory-sweep (e.g. bf16,int8); each pool is "
                         "held at the first dtype's device byte budget, "
                         "so int8 cells fit proportionally more blocks")
    ap.add_argument("--sweep-max-batch", type=int, default=4)
    ap.add_argument("--tp-sweep", action="store_true",
                    help="model the tensor-parallel dispatch/collective "
                         "sweep (inflection batch vs tp on LC vs CC) "
                         "instead of the measured batch sweep")
    ap.add_argument("--tps", default="1,2,4,8",
                    help="comma-separated tensor-parallel degrees for "
                         "--tp-sweep")
    ap.add_argument("--spec-sweep", action="store_true",
                    help="run the speculative-decoding k x batch sweep "
                         "(measured acceptance + modeled LC-vs-CC draft "
                         "launch tax) instead of the measured batch sweep")
    ap.add_argument("--spec-ks", default="0,2,4,8",
                    help="comma-separated speculation depths for "
                         "--spec-sweep (0 = plain decode baseline)")
    ap.add_argument("--model-batches", default="",
                    help="extra batch sizes to price (not serve) in "
                         "--spec-sweep, e.g. 16,64,256")
    ap.add_argument("--attribution-report", action="store_true",
                    help="print the per-operator launch/queue/%%-of-TKLQT "
                         "table for each batch point (needs a launch-plan "
                         "mode, not --plan jit)")
    args = ap.parse_args()
    use_compile_cache()
    if args.attribution_report and args.plan == "jit":
        ap.error("--attribution-report needs a launch-plan mode (--plan "
                 "eager/chain/auto/whole_graph/fused): plan=jit has no "
                 "kernel-level provenance to attribute")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.tp_sweep:
        # trace-only sweep: abstract weights — full-size archs price
        # without materializing (or randomly initializing) parameters
        from repro.launch.steps import params_sds
        sweep = tp_sweep(
            cfg, params_sds(cfg),
            batches=[int(b) for b in args.batches.split(",") if b],
            tps=[int(t) for t in args.tps.split(",") if t],
            platforms=[p for p in args.sweep_platforms.split(",") if p],
            max_len=args.max_len)
        for r in sweep["points"]:
            print(f"{r['platform']:<12s} {r['coupling']:<3s} "
                  f"tp={r['tp']:<2d} batch={r['batch']:<3d} "
                  f"tklqt={r['modeled_tklqt_us']}us "
                  f"step={r['modeled_step_us']}us "
                  f"launch={r['launch_tax_us']}us "
                  f"coll={r['collective_bytes']}B "
                  f"coll_tax={r['modeled_collective_tax_us']}us")
        for plat, by_tp in sweep["inflection_batch"].items():
            print(f"inflection[{plat}]: " + ", ".join(
                f"tp={t} -> {b}" for t, b in by_tp.items()))
        os.makedirs(args.out_dir, exist_ok=True)
        path = os.path.join(args.out_dir, "tp_sweep.json")
        with open(path, "w") as f:
            json.dump(sweep, f, indent=2)
        print(json.dumps({"summary": sweep, "artifacts": {"sweep": path}}))
        return

    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.spec_sweep:
        batches = [int(b) for b in args.batches.split(",") if b]
        mb = [int(b) for b in args.model_batches.split(",") if b]
        sweep = spec_sweep(
            cfg, params,
            ks=[int(k) for k in args.spec_ks.split(",") if k],
            batches=batches,
            platforms=[p for p in args.sweep_platforms.split(",") if p],
            scenario=args.scenario, n_requests=args.requests,
            seed=args.seed, prompt_cap=args.prompt_cap or None,
            output_cap=args.output_cap or None, max_len=args.max_len,
            model_batches=sorted(set(batches) | set(mb)) if mb else None)
        for r in sweep["measured"]:
            print(f"measured k={r['k']:<2d} batch={r['batch']:<3d} "
                  f"accept={r['accept_rate']:<5} "
                  f"steps/tok={r['steps_per_emitted_token']:<5} "
                  f"rounds={r['spec_rounds']:<4d} "
                  f"draft_disp={r['draft_dispatches']}")
        for r in sweep["modeled"]:
            print(f"{r['platform']:<12s} {r['coupling']:<3s} "
                  f"k={r['k']:<2d} batch={r['batch']:<5d} "
                  f"base/tok={r['modeled_baseline_per_token_us']}us "
                  f"spec/tok={r['modeled_spec_per_token_us']}us "
                  f"draft_tax={r['modeled_draft_launch_tax_per_round_us']}"
                  f"us win={r['win']}")
        for plat, by_k in sweep["win_batches"].items():
            print(f"win_batches[{plat}]: " + ", ".join(
                f"k={k} -> {bs}" for k, bs in by_k.items()))
        os.makedirs(args.out_dir, exist_ok=True)
        path = os.path.join(args.out_dir, "spec_sweep.json")
        with open(path, "w") as f:
            json.dump(sweep, f, indent=2)
        print(json.dumps({"summary": sweep, "artifacts": {"sweep": path}}))
        return
    if args.memory_sweep:
        sweep = memory_pressure_sweep(
            cfg, params, scenario=args.scenario,
            platforms=[p for p in args.sweep_platforms.split(",") if p],
            pool_fracs=[float(f) for f in args.pool_fracs.split(",") if f],
            kv_dtypes=[d for d in args.kv_dtypes.split(",") if d],
            max_batch=args.sweep_max_batch, max_len=args.max_len,
            block_size=args.block_size, n_requests=args.requests,
            seed=args.seed, prompt_cap=args.prompt_cap or None,
            output_cap=args.output_cap or None)
        for r in sweep["points"]:
            print(f"{r['platform']:<12s} {r['coupling']:<3s} "
                  f"link={r['link_gbps']}GB/s {r['kv_dtype']:<4s} "
                  f"pool={r['pool_frac']:<5} blocks={r['num_blocks']:<4d} "
                  f"preempt={r['preemptions']:<3d} "
                  f"offload={r['offload_bytes']}B "
                  f"tax={r['modeled_offload_tax_us']}us "
                  f"tax/tok={r['offload_tax_per_token_us']}us")
        for d in sweep["kv_dtype_deltas"]:
            print(f"delta[{d['platform']} pool={d['pool_frac']}] "
                  f"{d['baseline']}->{d['kv_dtype']}: "
                  f"capacity x{d['capacity_ratio']} "
                  f"preempt {d['preemptions'][d['baseline']]}->"
                  f"{d['preemptions'][d['kv_dtype']]} "
                  f"tax_delta={d['offload_tax_delta_us']}us")
        os.makedirs(args.out_dir, exist_ok=True)
        path = os.path.join(args.out_dir, "memory_sweep.json")
        with open(path, "w") as f:
            json.dump(sweep, f, indent=2)
        print(json.dumps({"summary": sweep, "artifacts": {"sweep": path}}))
        return
    workload = load_workload(args.replay) if args.replay else None
    batches = [int(b) for b in args.batches.split(",")]

    result = characterize(
        cfg, params, scenario=args.scenario, batches=batches,
        plan=args.plan, platform=args.platform, n_requests=args.requests,
        seed=args.seed, prompt_cap=args.prompt_cap or None,
        output_cap=args.output_cap or None, time_scale=args.time_scale,
        max_len=args.max_len, warmup=not args.no_warmup,
        workload=workload)

    for p in result.points:
        cls = result.boundedness.classify(p.batch)
        r = p.row()
        print(f"batch={p.batch:<3d} {cls:<9s} "
              f"launch_tax/step={r['decode_launch_tax_us']}us "
              f"step={r['mean_decode_step_us']}us "
              f"ttft_p50={r['ttft_p50_ms']}ms "
              f"ttft_p99={r['ttft_p99_ms']}ms "
              f"itl_p50={r['itl_p50_ms']}ms "
              f"itl_p99={r['itl_p99_ms']}ms "
              f"tok/s={r['tokens_per_s']}")
    infl = result.boundedness.inflection_batch
    print(f"inflection_batch={infl} "
          f"({'always CPU/dispatch-bound in range' if infl is None else 'GPU/compute-bound from here'})")

    if args.attribution_report:
        for p in result.points:
            rep = p.attribution
            if rep is None:
                print(f"attribution[batch={p.batch}]: unavailable "
                      "(no planned decode ran at this point)")
                continue
            print(f"attribution[batch={p.batch}] "
                  f"events={rep.total_events} complete={rep.complete} "
                  f"tklqt={rep.tklqt_s * 1e6:.1f}us")
            print(f"  {'operator':<12s} {'launches':>9s} {'launch_us':>10s} "
                  f"{'queue_us':>9s} {'exec_us':>9s} {'tklqt%':>7s}")
            for row in rep.as_dicts():
                print(f"  {row['operator']:<12s} {row['launches']:>9.1f} "
                      f"{row['launch_us']:>10.2f} {row['queue_us']:>9.2f} "
                      f"{row['exec_us']:>9.2f} {row['tklqt_pct']:>7.2f}")

    paths = write_artifacts(result, args.out_dir)
    print(json.dumps({"summary": result.summary(), "artifacts": paths}))


if __name__ == "__main__":
    main()
