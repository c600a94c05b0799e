"""Run one benchmark cell on the accelerator and print one JSON result line.

    python3 chipbench/run.py --workload smollm-chat --seed 7 --seconds 40 --trace 0

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` (see ``harness/spec.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  Either way
the run ends by checking what the timed path served against the plain
reference, and prints each number compared beside its limit, last on
standard error and last in the result line.

There is no CPU path: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench_trace"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 10.0


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def require_chips(n: int) -> list:
    """The TPU devices, or exit: a run never falls back to the CPU."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not /tmp/tpu_logs
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: needs a TPU; JAX found {len(devs)} "
                 f"{devs[0].platform} device(s)")
    if len(devs) < n:
        sys.exit(f"chipbench: the cell needs {n} chips; JAX found {len(devs)}")
    return devs


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping even small programs, so
    that only a checkout's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def breakdown(trace, run) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by what the harness was doing in them."""
    from chipbench.harness.trace import STEP_PROGRAMS
    totals: dict = {}
    for dev in trace.devices:
        for kind in STEP_PROGRAMS:
            progs = trace.programs(dev, kind)
            for op in trace.ops_within(dev, progs):
                key = f"{kind} {short_scope(op[3])} {op[2]}"
                totals[key] = totals.get(key, 0.0) + (op[1] - op[0]) * 1e-9
    n = max(1, len(trace.devices))
    ops = sorted(((k, v / n) for k, v in totals.items()),
                 key=lambda kv: -kv[1])[:10]
    dev0 = min(trace.devices, default=None)
    gaps = [] if dev0 is None else sorted(trace.idle_gaps(dev0),
                                          key=lambda g: g[0] - g[1])[:10]
    idle = [[trace.host_label((a + b) / 2), (b - a) * 1e-9] for a, b in gaps]
    return {"device_ops": [list(o) for o in ops], "idle_gaps": idle}


def short_scope(scope: str) -> str:
    """An op's name scope without the program's name and the layer loop's
    own levels: ``slot0/attn/gather``, ``while/body/dynamic_slice``."""
    scope = re.sub(r"^jit\([^)]*\)/", "", scope.rstrip(":"))
    return scope.replace("while/body/closed_call/", "")[:100] or "-"


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, base: Path, devices: list,
             trace_dir: Path = TRACE_DIR, keep_trace: bool = False,
             t_process: float = T_PROCESS) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    from chipbench.harness import cell as cellmod
    from chipbench.harness import check, spec
    from chipbench.harness.trace import TraceView
    model = spec.load_config(cell["config"], base)
    mix = spec.load_traffic(cell["traffic"], base)
    kind = devices[0].device_kind
    run = cellmod.Run(cell=cell["name"], model=model, mix=mix,
                      chips=cell["chips"],
                      peaks=spec.load_peaks(kind, base))
    tdir = None
    if trace:
        tdir = Path(trace_dir) / cell["name"]
        shutil.rmtree(tdir, ignore_errors=True)
    peak = cellmod.execute(run, seed, seconds, t_process, trace_dir=tdir,
                           trace_seconds=TRACE_SECONDS)
    tv = TraceView.from_dir(tdir) if trace else None
    if trace and not keep_trace:
        shutil.rmtree(tdir, ignore_errors=True)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(bench, cell["name"], section):
        v = spec.metric_reader(m["name"], base)(run, tv)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    due = run.due_in_window()
    failed = sum(1 for r in due if not r.stamps)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": None, "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        used = sorted(tv.devices)[:cell["chips"]]
        if used:
            device["busy_s"] = sum(tv.busy_ns(d) for d in used) * 1e-9 \
                / len(used)
        device["window_s"] = tv.seconds
        out["breakdown"] = breakdown(tv, run)
    lat = sorted(run.lateness)
    print(f"chipbench: {cell['name']} seed {seed}: requests sent "
          f"{len(run.reqs)}, due in window {len(due)}, failed {failed}; "
          f"generator lateness p50 "
          f"{(lat[len(lat) // 2] if lat else 0) * 1e3:.3f} ms max "
          f"{(lat[-1] if lat else 0) * 1e3:.3f} ms; compiles or cache "
          f"loads in window {run.compiles_in_window}; set-up phases "
          + ", ".join(f"{k} {v:.3f} s" for k, v in run.phases.items()),
          file=sys.stderr)
    t0 = time.perf_counter()
    readings = check.served_readings(model, seed, run.reqs, base)
    out["correct"] = check.passes(readings)
    print(f"chipbench: reference check took {time.perf_counter() - t0:.1f} s "
          f"on {jax.devices()[0].platform}", file=sys.stderr)
    for name, r in readings.items():
        print(f"check {name} {r['value']} {r['rule']} {r['limit']}",
              file=sys.stderr)
    out["checks"] = {k: {"value": r["value"], "limit": r["limit"]}
                     for k, r in readings.items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    _paths()
    from chipbench.harness import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, args.workload)
    devices = require_chips(cell["chips"])
    use_compile_cache()
    out = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                   spec.BENCH_DIR, devices)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
