"""Host time per engine tick, with the profiler off and on.

    python3 chipbench/span_overhead.py --src . --ticks 2000
    python3 chipbench/span_overhead.py --src <checkout of another commit>

Serves a tiny model (``tests/data/tiny.json``: four slots, 32-token
prompts, 200-token outputs, a new request whenever one ends) with the
program found under ``<src>/src``, so its ticks are short and their time
is mostly the host's: preparing inputs, launching the step programs,
waiting for the logits, bookkeeping.  After warm-up it times
``--ticks`` ticks with the profiler off, as many with a trace being
captured, and as many off again, and prints one JSON line with the
median and mean of each tick's wall time and of the thread's CPU time,
in microseconds.  Running it on two checkouts, in turn on one chip,
gives what the program's spans cost per tick.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PROMPT, OUTPUT = 32, 200


def timed_ticks(eng, n: int, feed) -> dict:
    wall, cpu = [], []
    for _ in range(n):
        feed()
        w, c = time.perf_counter(), time.thread_time()
        eng.tick()
        cpu.append(time.thread_time() - c)
        wall.append(time.perf_counter() - w)
    return {"wall_median_us": statistics.median(wall) * 1e6,
            "wall_mean_us": statistics.fmean(wall) * 1e6,
            "cpu_median_us": statistics.median(cpu) * 1e6,
            "cpu_mean_us": statistics.fmean(cpu) * 1e6}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--src", type=Path, default=ROOT,
                    help="checkout whose src/ holds the program to time")
    ap.add_argument("--ticks", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(args.src.resolve() / "src"), str(ROOT)]
    from chipbench import run as runmod
    from chipbench.harness import cell as cellmod
    from chipbench.harness import spec
    from chipbench.harness.traffic import prompt_tokens
    runmod.require_chips(1)
    runmod.use_compile_cache()
    import jax
    from repro.inference import engine
    from repro.inference.engine import Request
    model = spec.load_json(ROOT / "chipbench" / "tests" / "data" / "tiny.json")
    eng = cellmod.build_engine(model, args.seed)
    cellmod.warm_up(eng, {"prompt": {"min": PROMPT, "max": PROMPT}}, args.seed)
    vocab, rid = eng.cfg.vocab_size, [0]

    def feed():
        while eng.queue_depth < eng.B:
            eng.submit(Request(rid[0], prompt=prompt_tokens(
                args.seed, rid[0], PROMPT, vocab), max_new_tokens=OUTPUT))
            rid[0] += 1

    timed_ticks(eng, args.ticks // 4, feed)           # steady state
    out = {"program": engine.__file__,
           "device": jax.devices()[0].device_kind, "ticks": args.ticks,
           "off": timed_ticks(eng, args.ticks, feed)}
    tdir = ROOT / ".chipbench_trace" / "span_overhead"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    out["on"] = timed_ticks(eng, args.ticks, feed)
    jax.profiler.stop_trace()
    shutil.rmtree(tdir, ignore_errors=True)
    out["off_again"] = timed_ticks(eng, args.ticks, feed)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
