import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import — jax locks the device
count at first init.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-20b \
        --shape train_4k [--multi-pod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per cell, writes JSON with memory_analysis, cost_analysis, the HLO-parsed
roofline terms, and the collective schedule summary.
"""
import argparse
import json
import time
import traceback

from repro.configs import ASSIGNED, SHAPES, applicable_shapes, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build_roofline, model_flops
from repro.launch.steps import StepOptions, build_step
from repro.models import active_param_count
from repro.launch.compile_cache import use_compile_cache


def embed_param_count(cfg) -> int:
    n = cfg.vocab_size * cfg.d_model
    return n if cfg.tie_embeddings else 2 * n


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             options: StepOptions | None = None, out_dir: str | None = None,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = build_step(cfg, shape, mesh, options=options)
    lowered = bundle.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
    }
    p_sds = bundle.in_sds[0]
    n_active = active_param_count(p_sds, cfg)
    mf = model_flops(cfg, shape, n_active, embed_param_count(cfg))
    hlo_text = compiled.as_text()
    roof = build_roofline(compiled, cfg, shape, mesh,
                          model_flops_total=mf, hlo_text=hlo_text)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": mem_d,
        "roofline": roof.to_dict(),
        "options": None if options is None else options.__dict__,
    }
    if verbose:
        r = rec["roofline"]
        print(f"[{arch} x {shape_name} x {rec['mesh']}] ok "
              f"compile={t_compile:.0f}s "
              f"tC={r['t_compute']*1e3:.2f}ms tM={r['t_memory']*1e3:.2f}ms "
              f"tX={r['t_collective']*1e3:.2f}ms dom={r['dominant']} "
              f"useful={r['useful_flops_frac']:.2f} "
              f"roofline={r['roofline_frac']:.3f} "
              f"temp={mem_d['temp_bytes']/1e9:.1f}GB", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = f"{arch}__{shape_name}__{rec['mesh']}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cells = []
    if args.all:
        for arch in ASSIGNED:
            for sh in applicable_shapes(get_config(arch)):
                cells.append((arch, sh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch, sh in cells:
        for mp in meshes:
            tag = f"{arch}__{sh}__{'2x16x16' if mp else '16x16'}"
            if args.skip_existing and os.path.exists(
                    os.path.join(args.out, tag + ".json")):
                print(f"[{tag}] exists, skipping", flush=True)
                continue
            try:
                run_cell(arch, sh, multi_pod=mp, out_dir=args.out)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((tag, repr(e)))
                print(f"[{tag}] FAILED: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall cells ok")


if __name__ == "__main__":
    main()
