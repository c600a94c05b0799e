"""Sharding rules, multi-device lowering, EP equivalence, compression,
elastic restore — multi-device cases run in subprocesses with a forced
host-platform device count (the main test process keeps 1 device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import valid_spec
from repro.launch.mesh import make_host_mesh


def _run_sub(code: str, devices: int = 8) -> str:
    # forcing a host-platform device count only works on the CPU backend;
    # on an accelerator backend we need that many real devices
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


# ------------------------------------------------------------ spec fallback
def test_valid_spec_divisibility_fallback():
    mesh = make_host_mesh(data=1, model=1)
    # with 1-device axes everything divides
    assert valid_spec((15, 8), P("data", "model"), mesh) == P("data", "model")


def test_param_specs_smollm_heads_replicated():
    code = """
    import jax
    from repro.configs import get_config
    from repro.distributed.sharding import param_specs
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import params_sds
    mesh = make_host_mesh(data=2, model=4)
    cfg = get_config("smollm-360m")          # 15 heads: not divisible by 4
    p = params_sds(cfg)
    specs = param_specs(p, cfg, mesh)
    wq = specs["blocks"]["slot0"]["mixer"]["wq"]
    w_in = specs["blocks"]["slot0"]["mlp"]["w_in"]
    print("WQ", wq)
    print("WIN", w_in)
    """
    out = _run_sub(code)
    assert "WQ PartitionSpec(None, None, None)" in out     # replicated
    assert "'model'" in out.split("WIN", 1)[1]             # d_ff sharded


# ------------------------------------------------------------ EP vs local
def test_moe_ep_matches_local_multidevice():
    code = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.layers.moe import MeshContext, moe_init, moe_local_fwd, moe_ep_fwd
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=2, model=4)
    cfg = reduced(get_config("moonshot-v1-16b-a3b"))
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_experts=8,
                                              capacity_factor=8.0))
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    dist = MeshContext(mesh=mesh, dp_axes=("data",), tp_axis="model")
    y_ref, aux_ref = moe_local_fwd(params, x, cfg)
    for mode in ("seq", "rep"):
        y, aux = jax.jit(lambda p, x_: moe_ep_fwd(p, x_, cfg, dist, mode=mode))(params, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        print(mode, "err", err, "aux_err", abs(float(aux) - float(aux_ref)))
        assert err < 2e-4, (mode, err)
    print("EP_OK")
    """
    assert "EP_OK" in _run_sub(code)


# ------------------------------------------------------------ compression
def test_compressed_dp_grads_close_to_exact():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.compression import (
        init_error_state, make_compressed_dp_grad)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=4, model=1)
    w = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                          jnp.float32)}
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, 8)),
                    jnp.float32)
    def loss(p, b):
        return jnp.mean((b @ p["w"]) ** 2), 0.0
    step = make_compressed_dp_grad(loss, mesh, "data")
    errs = init_error_state(w)
    g, errs, _ = step(w, errs, x)
    g_exact = jax.grad(lambda p: loss(p, x)[0])(w)
    rel = float(jnp.linalg.norm(g["w"] - g_exact["w"]) /
                jnp.linalg.norm(g_exact["w"]))
    print("rel", rel)
    assert rel < 0.05, rel
    print("COMP_OK")
    """
    assert "COMP_OK" in _run_sub(code, devices=4)


# ------------------------------------------------------------ elastic
def test_elastic_restore_across_mesh_shapes():
    code = """
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.distributed.sharding import param_specs, shardings_for
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params
    cfg = reduced(get_config("internlm2-20b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    d = tempfile.mkdtemp()
    ckpt = CheckpointManager(d, async_write=False)
    ckpt.save(1, params)
    for shape in [(2, 4), (4, 2), (8, 1)]:
        mesh = make_host_mesh(data=shape[0], model=shape[1])
        sh = shardings_for(params, param_specs(params, cfg, mesh), mesh)
        restored = ckpt.restore(1, params, shardings=sh)
        leaf = jax.tree.leaves(restored)[0]
        ok = np.allclose(np.asarray(jax.tree.leaves(restored)[3]),
                         np.asarray(jax.tree.leaves(params)[3]))
        print(shape, "devices-used",
              len(leaf.sharding.device_set), "equal", ok)
        assert ok
    print("ELASTIC_OK")
    """
    assert "ELASTIC_OK" in _run_sub(code)


# ------------------------------------------------------------ lowering
def test_small_mesh_lowering_all_step_kinds():
    code = """
    import jax
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_step
    mesh = make_host_mesh(data=2, model=2, pod=2)
    for arch in ("internlm2-20b", "moonshot-v1-16b-a3b"):
        cfg = reduced(get_config(arch))
        for shape in (ShapeSpec("t", 64, 8, "train"),
                      ShapeSpec("p", 64, 4, "prefill"),
                      ShapeSpec("d", 64, 8, "decode")):
            c = build_step(cfg, shape, mesh).lower().compile()
            assert c.memory_analysis().temp_size_in_bytes >= 0
            print(arch, shape.kind, "ok")
    print("LOWER_OK")
    """
    assert "LOWER_OK" in _run_sub(code)
