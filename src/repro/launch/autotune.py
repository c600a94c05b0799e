"""Plan autotuner launcher: characterize -> benchmark candidates -> table.

    PYTHONPATH=src python -m repro.launch.autotune --arch smollm-360m \
        --reduced --scenario chatbot --batches 1,8 --requests 12 \
        --out-dir autotune-out

Runs the measured characterization sweep (default plan: ``eager``, the
paper's per-op dispatch stream), classifies each batch point CPU- or
GPU-bound from the measured decode-step curve, benchmarks the
region-appropriate candidate plans on the live ServeEngine, and writes:

  plan_table.json   the persisted winners — load with
                    ``ServeEngine(plan="autotuned", plan_table=...)``
                    or ``repro.launch.serve --plan autotuned
                    --plan-table plan_table.json``
  autotune.json     full summary: per-batch candidates + the
                    characterization sweep that gated them
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs import get_config, reduced
from repro.core.device_model import PLATFORMS
from repro.models import init_params
from repro.runtime.autotune import (CPU_BOUND_CANDIDATES,
                                    GPU_BOUND_CANDIDATES, autotune)
from repro.workload import list_scenarios, load_workload
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scenario", default="chatbot",
                    choices=list_scenarios())
    ap.add_argument("--batches", default="1,2,4,8",
                    help="comma-separated slot-pool sizes to autotune")
    ap.add_argument("--platform", default="TPU-v5e",
                    choices=sorted(PLATFORMS))
    ap.add_argument("--characterize-plan", default="eager",
                    help="plan driving the region-detection sweep "
                         "(eager = the paper's per-op dispatch stream)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-cap", type=int, default=24)
    ap.add_argument("--output-cap", type=int, default=8)
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--replay", default=None,
                    help="autotune over a recorded workload JSONL instead "
                         "of generating from the scenario")
    ap.add_argument("--out-dir", default="autotune-out")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    workload = load_workload(args.replay) if args.replay else None
    batches = [int(b) for b in args.batches.split(",")]

    result = autotune(
        cfg, params, scenario=args.scenario, batches=batches,
        platform=args.platform, characterize_plan=args.characterize_plan,
        n_requests=args.requests, seed=args.seed,
        prompt_cap=args.prompt_cap or None,
        output_cap=args.output_cap or None, time_scale=args.time_scale,
        max_len=args.max_len, workload=workload)

    for batch, entry in sorted(result.table.entries.items()):
        fam = (CPU_BOUND_CANDIDATES if entry.region == "CPU-bound"
               else GPU_BOUND_CANDIDATES)
        print(f"batch={batch:<3d} {entry.region:<9s} "
              f"candidates={','.join(fam)}")
        for c in sorted(entry.candidates,
                        key=lambda c: c.mean_decode_step_s):
            mark = "*" if c.plan == entry.selected else " "
            r = c.row()
            print(f"  {mark} {c.plan:<12s} "
                  f"step={r['mean_decode_step_us']}us "
                  f"tax={r['decode_launch_tax_us']}us "
                  f"disp/step={r['dispatches_per_decode_step']} "
                  f"fused/step={r['fused_dispatches_per_decode_step']} "
                  f"tok/s={r['tokens_per_s']}")

    os.makedirs(args.out_dir, exist_ok=True)
    table_path = result.table.save(
        os.path.join(args.out_dir, "plan_table.json"))
    summary_path = os.path.join(args.out_dir, "autotune.json")
    with open(summary_path, "w") as fh:
        json.dump(result.summary(), fh, indent=2, allow_nan=False)
    print(json.dumps({
        "selected": {str(b): e.selected
                     for b, e in sorted(result.table.entries.items())},
        "regions": {str(b): e.region
                    for b, e in sorted(result.table.entries.items())},
        "artifacts": {"plan_table": table_path, "summary": summary_path},
    }))


if __name__ == "__main__":
    main()
