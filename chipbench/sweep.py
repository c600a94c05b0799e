"""Find a paced cell's knee: several arrival rates after one set-up.

    python3 chipbench/sweep.py --workload smollm-chat --rates 4,6,8,10 \
        --seconds 20 --seed 5 [--out sweep.json]

Builds and warms the cell's engine once, then for each rate drives the
cell's traffic mix with only ``rate_rps`` changed (lead-in, window, a short
drain) and resets the engine.  Prints one JSON line per rate: the tails,
the tokens per second completed, and how many requests due in the window
were still waiting for a slot when it closed, the sign of a queue that
grows.  The knee is the highest rate whose queue does not grow; the cell's
traffic file then fixes a rate at about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    from chipbench import run as runmod
    from chipbench.harness import cell as cellmod
    from chipbench.harness import spec, traffic
    from chipbench.harness.driver import Driver
    from chipbench.harness.stats import percentile
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, args.workload)
    runmod.require_chips(cell["chips"])
    runmod.use_compile_cache()
    model = spec.load_config(cell["config"])
    base_mix = spec.load_traffic(cell["traffic"])
    t0 = time.perf_counter()
    eng = cellmod.build_engine(model, args.seed)
    cellmod.warm_up(eng, base_mix, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(base_mix, rate_rps=rate)
        reqs = traffic.generate(mix, args.seed, args.seconds)
        drv = Driver(eng, reqs, args.seed, eng.cfg.vocab_size)
        drv.start = time.perf_counter()
        t_open = drv.start + mix.get("lead_in_s", 0.0)
        t_close = t_open + args.seconds
        drv.run_until(t_close)
        due = [r for r in drv.sent if t_open <= r.due_abs < t_close]
        waiting = sum(1 for r in due if not r.stamps)
        drv.run_until(t_close + 5.0, accept=False,
                      until=lambda: all(r.stamps for r in due))
        ttft = [(r.stamps[0] if r.stamps else time.perf_counter())
                - r.due_abs for r in due]
        itl = [b - a for r in drv.sent for a, b in zip(r.stamps, r.stamps[1:])
               if t_open <= a and b < t_close]
        out = sum(1 for r in drv.sent for t in r.stamps
                  if t_open <= t < t_close)
        row = {"rate_rps": rate, "due": len(due),
               "waiting_at_close": waiting,
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "itl_p50_ms": percentile(itl, 50) * 1e3,
               "itl_p95_ms": percentile(itl, 95) * 1e3,
               "output_tok_per_s": out / args.seconds,
               "mean_slots_busy": sum(eng.stats.slot_occupancy)
               / max(1, len(eng.stats.slot_occupancy))}
        rows.append(row)
        print(json.dumps(row), flush=True)
        eng.reset()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "seconds": args.seconds, "rows": rows}, fh, indent=1)


if __name__ == "__main__":
    main()
