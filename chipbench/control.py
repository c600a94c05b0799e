"""Read the correctness check's two ends on the chip, for setting its limit.

    python3 chipbench/control.py --workload smollm-chat --seeds 1,2,3 \
        --seconds 10 [--fault NAME] [--out control.json]
    python3 chipbench/control.py --workload smollm-chat --seeds 1,2,3 \
        --witness [--out witness.json]

For each seed, in one process: a run of the cell's timed path at its own
load (its mix, a short window), then the comparison that the benchmark's
runs make (``check.served_readings`` and ``check.passes``) twice, on the
sample that a run draws: once of the program's served tokens, and once
with the control in the program's place, the tokens that the reference
computed in float8 puts first at the same positions, under the same
limit.  Each prints its numbers beside their limits and whether it is
correct; the control has to come out not correct.  With ``--fault NAME``
a fault of ``harness/faults.py`` is planted under the timed path and only
the program's comparison is made: it has to come out not correct too.

``--witness`` runs no engine.  At both gains of the q and k weights (1,
as served, and 3; ``weights.QK_GAIN``) it reads, over the last positions
of the prompts of each seed's first requests, the widest gap of the
tokens that the reference computed with bfloat16 inputs puts first: what
bfloat16 rounding alone does to the number compared.

The benchmark's runs never run any of this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
WITNESS_POSITIONS = 256


def readings_row(model: dict, run, seed: int, control: bool) -> dict:
    from chipbench.harness import check
    row = {}
    for who in ("program", "control") if control else ("program",):
        t0 = time.perf_counter()
        got = check.served_readings(model, seed, run.reqs,
                                    control=who == "control")
        ok = check.passes(got)
        for name, r in got.items():
            print(f"{who} seed {seed}: check {name} {r['value']} "
                  f"{r['rule']} {r['limit']}", file=sys.stderr)
        row[who] = {"correct": ok,
                    "checks": {k: {"value": r["value"], "limit": r["limit"]}
                               for k, r in got.items()},
                    "reference_s": time.perf_counter() - t0}
    return row


def witness(model: dict, mix: dict, seeds: list) -> list:
    import jax
    from chipbench.harness import check, spec, traffic, weights
    ref = spec.reference_module(model["reference"])
    rows = []
    for gain in (1.0, 3.0):
        weights.QK_GAIN = gain
        jax.clear_caches()
        for seed in seeds:
            key = weights.seed_key(seed)
            reqs = traffic.generate(mix, seed, 10.0)
            reqs = reqs[:model["check"]["sample_requests"]]
            seqs = [traffic.prompt_tokens(seed, r.rid, r.prompt_len,
                                          model["vocab_size"]) for r in reqs]
            pos = [list(range(max(0, len(s) - WITNESS_POSITIONS), len(s)))
                   for s in seqs]
            want, low = [], []
            for s, p in zip(seqs, pos):
                want += ref.logits_at(model, key, [s], [p], "f32")
                low += ref.logits_at(model, key, [s], [p], "bf16")
            g = check.gaps(want, [lg.argmax(-1) for lg in low])
            row = {"qk_gain": gain, "seed": seed, "positions": int(g.size),
                   "bf16_gap_max": float(g.max()),
                   "bf16_flipped": int((g > 0).sum())}
            rows.append(row)
            print(json.dumps(row), flush=True)
    weights.QK_GAIN = 1.0
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    from chipbench import run as runmod
    from chipbench.harness import cell as cellmod
    from chipbench.harness import faults, spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, args.workload)
    devices = runmod.require_chips(cell["chips"])
    runmod.use_compile_cache()
    model = spec.load_config(cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.witness:
        rows = witness(model, mix, seeds)
    else:
        if args.fault:
            cellmod.build_engine = faults.planted(cellmod.build_engine,
                                                  args.fault)
        rows = []
        for seed in seeds:
            run = cellmod.Run(cell=cell["name"], model=model, mix=mix,
                              chips=cell["chips"],
                              peaks=spec.load_peaks(devices[0].device_kind))
            cellmod.execute(run, seed, args.seconds, time.perf_counter())
            row = {"seed": seed, "fault": args.fault,
                   **readings_row(model, run, seed, not args.fault)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump({"workload": args.workload, "rows": rows}, fh, indent=1)


if __name__ == "__main__":
    main()
