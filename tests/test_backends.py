"""Scheduler/ExecutionBackend split: layer purity, tp=1 vs tp=2 token
equivalence (contiguous + paged + preempt->resume), per-device launch
accounting, mesh validation errors, and tensor-parallel plan pricing.

Multi-device cases run in subprocesses with a forced host-platform device
count (the main test process keeps 1 device), same as test_distributed."""
import subprocess
import sys
import textwrap
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.device_model import PLATFORMS, allreduce_cost_s
from repro.inference.engine import Request, ServeEngine
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.runtime.plan import LaunchPlan
from repro.runtime.planner import simulate_plan


def _run_sub(code: str, devices: int = 4) -> str:
    if jax.default_backend() != "cpu" and jax.device_count() < devices:
        pytest.skip(f"needs {devices} devices, have {jax.device_count()} "
                    f"on backend {jax.default_backend()!r}")
    repo = Path(__file__).resolve().parents[1]
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": str(repo / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=str(repo), timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=3, plen=6, new=4):
    rng = np.random.default_rng(0)
    return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, plen)),
                    max_new_tokens=new) for i in range(n)]


# ------------------------------------------------------------ layer purity
def test_scheduler_layer_is_device_free():
    """The acceptance bar of the refactor: no shard_map, mesh, or
    device-placement logic inside the scheduler module — all of that
    lives behind the ExecutionBackend protocol.  Checked on the AST so
    docstrings may still EXPLAIN the split."""
    import ast
    import inspect

    import repro.inference.engine as engine
    tree = ast.parse(inspect.getsource(engine))
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names.update(a.name for a in node.names)
    forbidden = {"shard_map", "make_mesh", "make_host_mesh", "Mesh",
                 "device_put", "NamedSharding", "PartitionSpec",
                 "jax.sharding", "repro.distributed.sharding",
                 "repro.launch.mesh", "repro.inference.backends.sharded"}
    hits = names & forbidden
    assert not hits, f"scheduler layer references {sorted(hits)}"


def test_backend_protocol_shape():
    from repro.inference.backends import ExecutionBackend, LocalBackend
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    be = LocalBackend(cfg, params, max_batch=1, max_len=16)
    assert isinstance(be, ExecutionBackend)
    assert be.info.kind == "local" and be.info.tp == 1


# ------------------------------------------------------------ mesh errors
def test_make_host_mesh_actionable_device_error():
    need = jax.device_count() + 1
    with pytest.raises(ValueError) as e:
        make_host_mesh(data=need, model=1)
    msg = str(e.value)
    assert "jax.device_count()" in msg
    if jax.default_backend() == "cpu":
        assert f"xla_force_host_platform_device_count={need}" in msg


def test_make_host_mesh_rejects_nonpositive_axes():
    with pytest.raises(ValueError, match="must be >= 1"):
        make_host_mesh(data=0, model=1)
    with pytest.raises(ValueError, match="must be >= 1"):
        make_host_mesh(data=1, model=-2)


def test_engine_tp_validation(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="tp must be >= 1"):
        ServeEngine(cfg, params, tp=0)
    # divisibility is checked before the mesh, so this works on 1 device
    with pytest.raises(ValueError, match="must divide n_heads"):
        ServeEngine(cfg, params, tp=3)
    # plan restriction is device-independent too
    with pytest.raises(ValueError, match="plan='jit' only"):
        ServeEngine(cfg, params, tp=2, plan="eager")
    if jax.device_count() < 2:
        with pytest.raises(ValueError, match="jax.device_count"):
            ServeEngine(cfg, params, tp=2)


# ------------------------------------------------------------ accounting
def test_local_backend_per_device_accounting(tiny):
    cfg, params = tiny
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32)
    eng.run(_requests(cfg))
    st = eng.stats
    assert st.tp == 1
    assert st.collectives == 0 and st.collective_bytes == 0
    assert st.per_device_dispatches == {
        0: st.prefill_dispatches + st.decode_dispatches}
    # reset() re-baselines the cumulative backend counters
    eng.reset()
    eng.run(_requests(cfg))
    st2 = eng.stats
    assert st2.per_device_dispatches == {
        0: st2.prefill_dispatches + st2.decode_dispatches}


# ------------------------------------------------------------ plan pricing
@dataclass
class _K:
    name: str
    flops: float
    bytes: float


def _kernels(n=6):
    return [_K(f"k{i}", 1e6, 1e4) for i in range(n)]


def test_simulate_plan_tp_multiplies_launch_and_divides_work():
    spec = PLATFORMS["Intel+H100"]
    ks = _kernels()
    plan = LaunchPlan.eager(len(ks))
    ev1 = simulate_plan(ks, plan, spec, tp=1)
    ev4 = simulate_plan(ks, plan, spec, tp=4)
    # per-device dispatch streams: host launch time x tp
    assert sum(e.t_launch for e in ev4) == pytest.approx(
        4 * sum(e.t_launch for e in ev1))
    # per-device work: kernel durations shrink (never grow) with tp
    assert sum(e.duration for e in ev4) < sum(e.duration for e in ev1)


def test_simulate_plan_collective_bytes_pricing():
    spec = PLATFORMS["GH200"]
    ks = _kernels()
    plan = LaunchPlan.eager(len(ks))
    base = simulate_plan(ks, plan, spec, tp=2)
    # scalar: one aggregate all-reduce after the final segment
    tot = simulate_plan(ks, plan, spec, tp=2, collective_bytes=1 << 20)
    extra = tot[-1].duration - base[-1].duration
    assert extra == pytest.approx(allreduce_cost_s(spec, 1 << 20, 2))
    # per-segment list localizes the latency floors
    per_seg = [0.0] * len(ks)
    per_seg[1] = per_seg[4] = 1 << 10
    loc = simulate_plan(ks, plan, spec, tp=2, collective_bytes=per_seg)
    want = 2 * allreduce_cost_s(spec, 1 << 10, 2)
    assert (sum(e.duration for e in loc) - sum(e.duration for e in base)
            == pytest.approx(want))
    with pytest.raises(ValueError, match="entries"):
        simulate_plan(ks, plan, spec, tp=2, collective_bytes=[1.0])
    with pytest.raises(ValueError, match="tp must be >= 1"):
        simulate_plan(ks, plan, spec, tp=0)


def test_allreduce_cost_model():
    lc, cc = PLATFORMS["Intel+H100"], PLATFORMS["GH200"]
    nbytes = 8 << 20
    assert allreduce_cost_s(lc, nbytes, 1) == 0.0
    # CC fabric (NVLink-C2C) beats LC (PCIe) at equal payload and degree
    assert allreduce_cost_s(cc, nbytes, 4) < allreduce_cost_s(lc, nbytes, 4)
    # cost grows with degree (more ring steps, more wire bytes/device)
    assert allreduce_cost_s(lc, nbytes, 8) > allreduce_cost_s(lc, nbytes, 2)
    with pytest.raises(ValueError):
        allreduce_cost_s(lc, -1.0, 2)


def test_tp_sweep_modeled_shift(tiny):
    cfg, params = tiny
    from repro.telemetry.characterize import tp_sweep
    sweep = tp_sweep(cfg, params, batches=(1, 2), tps=(1, 2),
                     platforms=("Intel+H100",), max_len=16)
    pts = {(p["tp"], p["batch"]): p for p in sweep["points"]}
    assert set(pts) == {(1, 1), (1, 2), (2, 1), (2, 2)}
    # host dispatch streams double with tp on the SAME kernel stream
    assert pts[(2, 1)]["n_kernels"] == pts[(1, 1)]["n_kernels"]
    assert pts[(2, 1)]["launch_tax_us"] == pytest.approx(
        2 * pts[(1, 1)]["launch_tax_us"], rel=1e-6)
    # collectives appear only at tp>1 and are priced over the link
    assert pts[(1, 1)]["collective_bytes"] == 0
    assert pts[(2, 1)]["collective_bytes"] > 0
    assert pts[(2, 1)]["modeled_collective_tax_us"] > 0
    assert "Intel+H100" in sweep["inflection_batch"]


# ------------------------------------------------------------ equivalence
def test_tp2_token_equivalence_all_cache_modes():
    """tp=2 ShardedBackend must emit byte-identical greedy tokens to the
    tp=1 LocalBackend on reduced smollm for cache='contiguous' AND
    cache='paged', including a preempt->resume case (tight pool, both
    recompute and host-offload restore), plus sane sharded stats."""
    code = """
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.inference.engine import Request, ServeEngine
    from repro.models import init_params

    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def reqs(n=4, plen=8, new=6):
        rng = np.random.default_rng(0)
        return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, plen)),
                        max_new_tokens=new) for i in range(n)]

    def toks(eng):
        done = eng.run(reqs())
        return [r.generated for r in sorted(done, key=lambda r: r.rid)]

    # contiguous
    c1 = toks(ServeEngine(cfg, params, max_batch=2, max_len=32))
    e2 = ServeEngine(cfg, params, max_batch=2, max_len=32, tp=2)
    c2 = toks(e2)
    assert c1 == c2, ("contiguous", c1, c2)
    st = e2.stats
    assert st.tp == 2
    assert set(st.per_device_dispatches) == {0, 1}
    assert st.decode_dispatches == 2 * st.decode_steps
    assert st.collectives > 0 and st.collective_bytes > 0
    assert st.modeled_collective_tax_s > 0
    print("CONTIG_OK")

    # paged, free pool
    kw = dict(max_batch=2, max_len=32, cache="paged", block_size=4)
    p1 = toks(ServeEngine(cfg, params, **kw))
    p2 = toks(ServeEngine(cfg, params, tp=2, **kw))
    assert p1 == p2, ("paged", p1, p2)
    print("PAGED_OK")

    # tight pool: preempt -> recompute resume
    kw = dict(max_batch=3, max_len=32, cache="paged", block_size=4,
              num_blocks=9, prefill_chunk=4)
    r1e = ServeEngine(cfg, params, **kw); r1 = toks(r1e)
    r2e = ServeEngine(cfg, params, tp=2, **kw); r2 = toks(r2e)
    assert r1 == r2, ("recompute", r1, r2)
    assert r1e.stats.preemptions > 0 and \\
        r1e.stats.preemptions == r2e.stats.preemptions
    print("PREEMPT_RECOMPUTE_OK")

    # tight pool: preempt -> host-offload restore (byte-exact KV restore
    # through the sharded pages)
    kw["offload"] = "host"
    o1e = ServeEngine(cfg, params, **kw); o1 = toks(o1e)
    o2e = ServeEngine(cfg, params, tp=2, **kw); o2 = toks(o2e)
    assert o1 == o2, ("offload", o1, o2)
    # head-sharded pages: each device stages 1/tp of the KV over its own
    # host link, so per-device staged bytes halve at tp=2
    assert o2e.stats.offload_bytes * 2 == o1e.stats.offload_bytes > 0
    assert o2e.stats.restore_bytes * 2 == o1e.stats.restore_bytes > 0
    print("PREEMPT_OFFLOAD_OK")

    # warmup -> reset -> measure keeps compiled shard_map fns and tokens
    o2e.reset()
    assert toks(o2e) == o1
    assert o2e.stats.per_device_dispatches[0] == \\
        o2e.stats.per_device_dispatches[1] > 0
    print("RESET_OK")
    """
    out = _run_sub(code)
    for marker in ("CONTIG_OK", "PAGED_OK", "PREEMPT_RECOMPUTE_OK",
                   "PREEMPT_OFFLOAD_OK", "RESET_OK"):
        assert marker in out


def test_sharded_serve_cli_reports_tp_counters():
    code = """
    import json, subprocess, sys
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "smollm-360m", "--reduced", "--requests", "3", "--max-batch", "2",
         "--max-new", "3", "--max-len", "64", "--tp", "2", "--no-warmup"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["tp"] == 2
    assert set(rep["per_device_dispatches"]) == {"0", "1"}
    assert rep["collective_bytes"] > 0
    assert rep["modeled_collective_tax_us"] > 0
    print("CLI_OK")
    """
    assert "CLI_OK" in _run_sub(code)


# ------------------------------------------------------------ placement
def test_local_backend_reports_its_device(tiny):
    cfg, params = tiny
    dev = jax.devices()[-1]
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, device=dev)
    assert eng.backend.info.devices == (dev.id,)
    for leaf in jax.tree.leaves((eng.backend.params, eng.cache)):
        assert leaf.devices() == {dev}
    with pytest.raises(ValueError, match="tp=1"):
        ServeEngine(cfg, params, max_batch=2, max_len=32, tp=2, device=dev)


def test_fleet_pins_replicas_to_devices():
    code = """
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.inference.engine import Request, ServeEngine
    from repro.inference.fleet import ReplicaFleet
    from repro.inference.router import RequestRouter
    from repro.models import init_params
    cfg = reduced(get_config("smollm-360m"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    def reqs():
        rng = np.random.default_rng(0)
        return [Request(i, prompt=list(rng.integers(0, cfg.vocab_size, 9)),
                        max_new_tokens=4) for i in range(8)]
    kw = dict(max_batch=2, max_len=32, cache="paged", block_size=4)
    fleet = ReplicaFleet(cfg, params, replicas=4, **kw)
    devs = [rep.engine.backend.info.devices for rep in fleet.live()]
    assert devs == [(0,), (1,), (2,), (3,)], devs
    for rep in fleet.live():
        (d,) = rep.engine.backend.info.devices
        for leaf in jax.tree.leaves((rep.engine.backend.params,
                                     rep.engine.cache)):
            assert {x.id for x in leaf.devices()} == {d}
    got = RequestRouter(fleet, policy="round-robin").route(reqs())
    ref = {r.rid: list(r.generated)
           for r in ServeEngine(cfg, params, **kw).run(reqs())}
    assert got.tokens_by_rid == ref
    # a fifth replica goes to the least-loaded device, lowest index first
    assert fleet.add_replica().engine.backend.info.devices == (0,)
    print("FLEET_DEVICES_OK")
    """
    assert "FLEET_DEVICES_OK" in _run_sub(code)


def test_init_sharded_params_builds_into_shardings():
    code = """
    import jax, numpy as np
    from repro.configs import get_config, reduced
    from repro.inference.backends.sharded import init_sharded_params
    from repro.inference.engine import ServeEngine
    from repro.models import init_params
    cfg = reduced(get_config("codeqwen1.5-7b"), n_layers=2)
    key = jax.random.PRNGKey(0)
    sharded = init_sharded_params(key, cfg, 4)
    whole = jax.jit(lambda: init_params(key, cfg))()
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(whole)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert len(a.sharding.device_set) == 4
    wq = sharded["blocks"]["slot0"]["mixer"]["wq"]
    assert wq.sharding.spec[-1] == "model"
    assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 4
    eng = ServeEngine(cfg, sharded, max_batch=2, max_len=32, tp=4)
    for leaf in jax.tree.leaves(eng.cache):
        assert len(leaf.sharding.device_set) == 4
    print("SHARDED_INIT_OK")
    """
    assert "SHARDED_INIT_OK" in _run_sub(code)


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(env_dir, tmp_path):
    code = f"""
    import os
    if {env_dir}:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache
    used = use_compile_cache()
    assert DEFAULT_CACHE_DIR.parent.joinpath("src", "repro").is_dir()
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    if {env_dir}:
        assert used == {str(tmp_path)!r}, used
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(4)).block_until_ready()
        assert os.listdir(used)
    else:
        assert used == str(DEFAULT_CACHE_DIR), used
    print("CACHE_OK")
    """
    assert "CACHE_OK" in _run_sub(code, devices=1)
