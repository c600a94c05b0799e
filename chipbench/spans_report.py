"""Traced runs of a cell, read with the program's own ``repro.*`` spans.

    python3 chipbench/spans_report.py --workload smollm-chat --seeds 11,12 \
        --seconds 10 --out-dir .chipbench_trace/spans

Each seed makes one run as ``run.py --trace 1`` does (set-up, lead-in, a
window whose first 10 s are traced, the drain), without the reference
check, and prints one JSON line:

- ``metrics``: the cell's per-layer metrics and the readers of the
  program's spans (``tklqt_ms``, ``launch_ms``, ``admit_to_token_ms``,
  ``token_holdback_ms``);
- ``idle``: the device idle time that ``host_gap_ms`` counts, split by
  the innermost ``repro.*`` span open on the host (``repro.tick`` passed
  over), else by the harness's driver span; ``attributed_share`` is the
  part that falls inside a program span below the tick or inside the
  driver's ``generator``, ``stamp`` or ``sleep``;
- ``reconcile``: per request whose first token the window saw, the
  driver's time to first token minus the program's ``queue_wait_ms`` and
  ``admit_to_token_ms`` minus the generator's lateness, in ms.

The trace view and the requests' stamps are written under ``--out-dir``
for reading again without the chip.  Like ``run.py`` it needs a TPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SPAN_METRICS = ("tklqt_ms", "launch_ms", "admit_to_token_ms",
                "token_holdback_ms")
DRIVER_SPANS = ("generator", "stamp", "sleep")


def idle_intervals(view, dev: int) -> list:
    """The device idle intervals that ``metrics/host_gap_ms.py`` counts:
    between consecutive step programs in the window, not covered by any
    operation, in gaps where the harness did not sleep."""
    from chipbench.harness.trace import merged
    sleeps = [(h[0], h[1]) for h in view.host_spans("sleep")]
    progs = sorted(view.programs(dev, "decode")
                   + view.programs(dev, "prefill"))
    busy = merged([(a, b) for a, b, *_ in view.devices[dev]["ops"]])
    ends = [b for _, b in busy]
    out = []
    for (_, end, _), (start, _, _) in zip(progs, progs[1:]):
        if start <= end or any(s < start and e > end for s, e in sleeps):
            continue
        cur = end
        for a, b in busy[bisect.bisect_right(ends, cur):]:
            if a >= start:
                break
            if a > cur:
                out.append((cur, a))
            cur = max(cur, b)
        if cur < start:
            out.append((cur, start))
    return out


def label_at(view, t: float) -> str:
    """What the host was doing at ``t``: the innermost program span below
    the tick, else the harness's driver span, else ``repro.tick`` if a
    tick was open, else the innermost harness span."""
    span = view.innermost(t)
    if span is not None:
        return "repro." + span[2]
    label = view.host_label(t)
    if label not in DRIVER_SPANS and \
            view.innermost(t, below_tick=False) is not None:
        return "repro.tick"
    return "chipbench." + label


def attribute_idle(view, chips: int = 1) -> dict:
    """Device idle time (``idle_intervals``) by ``label_at``, in ms summed
    over the chips used, with the share that is attributed."""
    edges = sorted({t for p in view.program for t in p[:2]}
                   | {t for h in view.host for t in h[:2]})
    by_label: dict = {}
    for dev in sorted(view.devices)[:chips]:
        for a, b in idle_intervals(view, dev):
            cuts = [a] + edges[bisect.bisect_right(edges, a):
                               bisect.bisect_left(edges, b)] + [b]
            for lo, hi in zip(cuts, cuts[1:]):
                key = label_at(view, (lo + hi) / 2)
                by_label[key] = by_label.get(key, 0.0) + (hi - lo) * 1e-6
    total = sum(by_label.values())
    good = sum(v for k, v in by_label.items()
               if (k.startswith("repro.") and k != "repro.tick")
               or k in {"chipbench." + s for s in DRIVER_SPANS})
    return {"total_ms": total,
            "attributed_share": good / total if total else None,
            "by_label_ms": dict(sorted(by_label.items(),
                                       key=lambda kv: -kv[1]))}


def reconcile(view, reqs: list, lateness: list) -> dict:
    """Driver TTFT minus (queue wait + admit-to-token) minus the
    generator's lateness, in ms, per request with a first-token span in
    the window (requests and lateness in send order)."""
    late = {r.rid: lat for r, lat in zip(reqs, lateness)}
    by_rid = {r.rid: r for r in reqs}
    rest = []
    for p in view.program_spans("first_token"):
        r = by_rid.get(p[3]["rid"])
        if r is None or not r.stamps:
            continue
        ttft_ms = (r.stamps[0] - r.due_abs) * 1e3
        rest.append(ttft_ms - p[3]["queue_wait_ms"]
                    - p[3]["admit_to_token_ms"] - late[r.rid] * 1e3)
    if not rest:
        return {"n": 0}
    return {"n": len(rest), "median_ms": statistics.median(rest),
            "min_ms": min(rest), "max_ms": max(rest)}


def read_metrics(bench: dict, cell: dict, run, view) -> dict:
    from chipbench.harness import spec
    names = [m["name"] for m in spec.cell_metrics(bench, cell["name"],
                                                  "per_layer")]
    names += [m for m in SPAN_METRICS if m not in names]
    return {n: spec.metric_reader(n)(run, view) for n in names}


def traced_run(bench: dict, cell: dict, seed: int, seconds: float,
               devices: list, out_dir: Path, t_process: float) -> dict:
    from chipbench.harness import cell as cellmod
    from chipbench.harness import spec
    from chipbench.harness.program_trace import ProgramTraceView
    from chipbench.run import TRACE_DIR, TRACE_SECONDS
    run = cellmod.Run(cell=cell["name"],
                      model=spec.load_config(cell["config"]),
                      mix=spec.load_traffic(cell["traffic"]),
                      chips=cell["chips"],
                      peaks=spec.load_peaks(devices[0].device_kind))
    tdir = TRACE_DIR / f"{cell['name']}-spans"
    shutil.rmtree(tdir, ignore_errors=True)
    t = time.perf_counter()
    cellmod.execute(run, seed, seconds, t_process, trace_dir=tdir,
                    trace_seconds=TRACE_SECONDS)
    t_run = time.perf_counter() - t
    view = ProgramTraceView.from_dir(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"chipbench: {cell['name']} seed {seed}: set-up {run.setup_s:.1f} "
          f"s, run {t_run:.1f} s, trace read "
          f"{time.perf_counter() - t - t_run:.1f} s", file=sys.stderr,
          flush=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    with gzip.open(out_dir / f"{cell['name']}-{seed}.json.gz", "wt") as fh:
        json.dump({"view": view.to_json(), "lateness": run.lateness,
                   "reqs": [[r.rid, r.due_abs, r.stamps[:1]]
                            for r in run.reqs]}, fh)
    used = sorted(view.devices)[:cell["chips"]]
    return {"cell": cell["name"], "seed": seed,
            "device": {"kind": devices[0].device_kind,
                       "busy_s": sum(view.busy_ns(d) for d in used) * 1e-9
                       / max(1, len(used)),
                       "window_s": view.seconds},
            "metrics": read_metrics(bench, cell, run, view),
            "idle": attribute_idle(view, cell["chips"]),
            "reconcile": reconcile(view, run.reqs, run.lateness),
            "compiles_in_window": run.compiles_in_window}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one traced run each")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out-dir", type=Path,
                    default=ROOT / ".chipbench_trace" / "spans")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import run as runmod
    from chipbench.harness import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, args.workload)
    devices = runmod.require_chips(cell["chips"])
    runmod.use_compile_cache()
    t_process = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        out = traced_run(bench, cell, seed, args.seconds, devices,
                         args.out_dir, t_process)
        print(json.dumps(out), flush=True)
        t_process = time.perf_counter()


if __name__ == "__main__":
    main()
