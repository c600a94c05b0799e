"""Tensor-parallel sharded serving: measured tp=1 vs tp=2 engine decode
steps on reduced smollm (byte-identical greedy tokens asserted), plus the
modeled per-layer collective tax of a full-size decode step on LC vs CC
coupling fabrics — the multi-GPU half of the serving story.

The tp pair runs in this process on its own devices and each row names
them.  An accelerator backend with fewer than two devices is an error,
never a fall back to the CPU.  Only when the CPU is the backend and this
process holds one device does the pair run in a child with virtual CPU
devices (the child imports this module and runs the same code)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import FAST, csv_row
from repro.configs import get_config
from repro.core.device_model import PLATFORMS, allreduce_cost_s
from repro.telemetry.characterize import decode_collective_sites

ARCH = "smollm-360m"
DEVICES = 4
MAX_LEN = 64
REQUESTS = 4 if FAST else 6
MAX_NEW = 4 if FAST else 8


def _pair_in_process() -> list[dict]:
    """tp=1 then tp=2 engines on this process's devices."""
    import jax
    import numpy as np
    from repro.configs import reduced
    from repro.inference.engine import Request, ServeEngine
    from repro.models import init_params

    cfg = reduced(get_config(ARCH), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    dev = jax.devices()[0]
    device = f"{dev.platform}:{dev.device_kind}x{jax.device_count()}"

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, 8)),
                        max_new_tokens=MAX_NEW) for i in range(REQUESTS)]

    def measure(tp):
        eng = ServeEngine(cfg, params, max_batch=2, max_len=MAX_LEN, tp=tp)
        eng.run(reqs())                 # warmup: pay jit/shard_map compiles
        eng.reset()
        done = eng.run(reqs())
        toks = [r.generated for r in sorted(done, key=lambda r: r.rid)]
        st = eng.stats
        steps = st.step_times_s
        return toks, {
            "tp": tp, "device": device,
            "mean_step_us": 1e6 * sum(steps) / len(steps) if steps else 0.0,
            "decode_steps": st.decode_steps,
            "decode_dispatches": st.decode_dispatches,
            "per_device": st.per_device_dispatches,
            "collective_bytes_per_step": st.collective_bytes_per_decode_step,
            "modeled_collective_tax_us": st.modeled_collective_tax_s * 1e6,
        }

    t1, r1 = measure(1)
    t2, r2 = measure(2)
    assert t1 == t2, ("tp=2 tokens diverged from tp=1", t1, t2)
    return [r1, r2]


def _measure_tp_pair() -> list[dict]:
    import jax
    if jax.device_count() >= 2:
        return _pair_in_process()
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"sharded_decode needs 2 {jax.default_backend()} devices, "
            f"found {jax.device_count()}")
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={DEVICES}",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([str(root / "src"), str(root)])})
    code = ("import json\n"
            "from benchmarks.bench_sharded_decode import _pair_in_process\n"
            "for r in _pair_in_process():\n"
            "    print('ROW', json.dumps(r))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=root, timeout=540)
    if out.returncode != 0:
        raise RuntimeError(f"sharded child failed: {out.stderr[-2000:]}")
    return [json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines() if line.startswith("ROW")]


def run() -> list[str]:
    rows = []
    for r in _measure_tp_pair():
        per_dev = ";".join(f"d{d}={n}" for d, n in
                           sorted(r["per_device"].items()))
        rows.append(csv_row(
            f"sharded_decode/engine_tp{r['tp']}", r["mean_step_us"],
            f"decode_steps={r['decode_steps']};"
            f"dispatches={r['decode_dispatches']};{per_dev};"
            f"coll_B_per_step={r['collective_bytes_per_step']:.0f};"
            f"coll_tax_us={r['modeled_collective_tax_us']:.1f};"
            f"device={r['device']};tokens=byte-identical-vs-tp1"))

    # modeled: per-step collective tax of FULL smollm decode, LC vs CC —
    # the same per-layer psum payloads the sharded backend captures,
    # priced per coupling fabric (no weights materialized)
    cfg = get_config(ARCH)
    batch, tp = 8, 2
    sites = [c for c in decode_collective_sites(cfg, batch, 2 * cfg.n_layers)
             if c]
    for plat in ("Intel+H100", "GH200"):
        spec = PLATFORMS[plat]
        tax = sum(allreduce_cost_s(spec, c, tp) for c in sites)
        rows.append(csv_row(
            f"sharded_decode/allreduce_tax_{spec.coupling}", 0.0,
            f"platform={plat};arch={cfg.name};batch={batch};tp={tp};"
            f"psums={len(sites)};payload_B={int(sum(sites))};"
            f"modeled_tax_us={tax * 1e6:.1f}"))
    return rows
