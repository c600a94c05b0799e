"""Described-chip compiles of the served kernels at smollm-360m widths.

Each test lowers a Pallas kernel with ``interpret=False`` for one chip of a
described (not attached) TPU v5e and compiles it with the TPU compiler, so a
tile layout or VMEM budget the chip refuses fails here, on the CPU, instead
of on the chip.  Nothing runs: these tests say nothing about results or
times.  The topology is described inside a fixture (never at import), and
the persistent compilation cache is off for this file, since a compile for a
described chip can be written to it but never read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.fused import residual_rmsnorm, rmsnorm_matmul

CFG = get_config("smollm-360m")
BATCH, PAGES, BLOCK, MAX_LEN = 8, 512, 16, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sharding, page_dtype):
    hq, hkv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    return (_sds((BATCH, hq, hd), jnp.bfloat16, sharding),
            _sds((PAGES, BLOCK, hkv, hd), page_dtype, sharding),
            _sds((PAGES, BLOCK, hkv, hd), page_dtype, sharding),
            _sds((BATCH, MAX_LEN // BLOCK), jnp.int32, sharding),
            _sds((BATCH,), jnp.int32, sharding))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_paged_decode_bf16_compiles(one_chip):
    args = _paged_args(one_chip, jnp.bfloat16)
    compiled = paged_decode_attention.lower(
        *args, scale=CFG.hd ** -0.5, interpret=False).compile()
    assert _has_kernel(compiled)


def test_paged_decode_int8_compiles(one_chip):
    args = _paged_args(one_chip, jnp.int8)
    scales = _sds((PAGES, BLOCK, CFG.n_kv_heads), jnp.float32, one_chip)
    compiled = paged_decode_attention.lower(
        *args, scale=CFG.hd ** -0.5, interpret=False,
        k_scale=scales, v_scale=scales).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("f", [1600, CFG.d_ff, CFG.vocab_size])
def test_rmsnorm_matmul_compiles(one_chip, f):
    d = CFG.d_model
    compiled = rmsnorm_matmul.lower(
        _sds((BATCH, d), jnp.bfloat16, one_chip),
        _sds((d,), jnp.bfloat16, one_chip),
        _sds((d, f), jnp.bfloat16, one_chip),
        eps=CFG.norm_eps, interpret=False).compile()
    assert _has_kernel(compiled)


def test_residual_rmsnorm_compiles(one_chip):
    d = CFG.d_model
    x = _sds((BATCH, d), jnp.bfloat16, one_chip)
    compiled = residual_rmsnorm.lower(
        x, _sds((d,), jnp.bfloat16, one_chip), x,
        eps=CFG.norm_eps, interpret=False).compile()
    assert _has_kernel(compiled)
