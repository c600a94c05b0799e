"""Pipeline parallelism and the int8 KV cache."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.inference.kv_quant import (
    make_quantized_cache, read_kv, write_kv)


def _run_sub(code: str, devices: int = 4) -> str:
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


# ------------------------------------------------------------ pipeline
def test_pipeline_matches_sequential_multidevice():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_forward, reference_forward
    P, n_micro, mb, d = 4, 6, 2, 8
    mesh = jax.make_mesh((P,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (P, d, d)) * 0.3,
              "b": jax.random.normal(key, (P, d)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    stage_fn = lambda p, x_: jnp.tanh(x_ @ p["w"] + p["b"])
    y = pipeline_forward(stage_fn, params, x, mesh)
    ref = reference_forward(stage_fn, params, x)
    err = float(jnp.max(jnp.abs(y - ref)))
    print("pp err", err)
    assert err < 1e-5
    print("PP_OK")
    """
    assert "PP_OK" in _run_sub(code)


def test_pipeline_explicit_mesh_multidevice():
    """A ``pipe`` axis of the default (Explicit) type: the last stage's
    gather names its output sharding instead of leaving it ambiguous."""
    code = """
    import jax, jax.numpy as jnp
    from repro.distributed.pipeline import pipeline_forward, reference_forward
    mesh = jax.make_mesh((4,), ("pipe",))
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.3}
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 8))
    stage_fn = lambda p, x_: jnp.tanh(x_ @ p["w"])
    y = pipeline_forward(stage_fn, params, x, mesh)
    assert float(jnp.max(jnp.abs(y - reference_forward(stage_fn, params, x)))) < 1e-5
    print("PPX_OK")
    """
    assert "PPX_OK" in _run_sub(code)


def test_pipeline_single_stage_degenerates():
    code = """
    import jax, jax.numpy as jnp
    from repro.distributed.pipeline import pipeline_forward, reference_forward
    mesh = jax.make_mesh((1,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    params = {"w": jnp.eye(4)[None] * 2.0}
    x = jnp.ones((3, 2, 4))
    y = pipeline_forward(lambda p, x_: x_ @ p["w"], params, x, mesh)
    assert jnp.allclose(y, 2 * x)
    print("PP1_OK")
    """
    assert "PP1_OK" in _run_sub(code, devices=1)


# ------------------------------------------------------------ int8 KV
def test_kv_quant_roundtrip_accuracy():
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (2, 8, 4, 16))
    cache = make_quantized_cache(2, 32, 4, 16)
    cache = write_kv(cache, k, k * 0.5, jnp.asarray(0, jnp.int32))
    kd, vd = read_kv(cache, jnp.float32)
    rel = float(jnp.max(jnp.abs(kd[:, :8] - k)) / jnp.max(jnp.abs(k)))
    assert rel < 0.02, rel           # int8 symmetric: <2% relative error


def test_kv_quant_attention_close_to_fp():
    """Decode attention over an int8 cache matches the fp cache closely."""
    from repro.kernels.decode_attention.ref import decode_attention_ref
    key = jax.random.PRNGKey(1)
    B, H, T, hd = 2, 4, 32, 16
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(3), (B, T, H, hd))
    cache = make_quantized_cache(B, T, H, hd)
    cache = write_kv(cache, k, v, jnp.asarray(0, jnp.int32))
    kd, vd = read_kv(cache, jnp.float32)
    o_q = decode_attention_ref(q, kd.transpose(0, 2, 1, 3),
                               vd.transpose(0, 2, 1, 3), T, scale=0.25)
    o_f = decode_attention_ref(q, k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3), T, scale=0.25)
    assert float(jnp.max(jnp.abs(o_q - o_f))) < 0.05


