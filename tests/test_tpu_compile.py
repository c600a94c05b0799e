"""Described-chip compiles of the served kernels at smollm-360m widths.

Each test lowers a Pallas kernel with ``interpret=False`` for one chip of a
described (not attached) TPU v5e and compiles it with the TPU compiler, so a
tile layout or VMEM budget the chip refuses fails here, on the CPU, instead
of on the chip.  The served step programs are compiled the same way at the
smollm-chat benchmark cell's shapes, to check what the compiler makes of the
paged pool in the layer loop.  Nothing runs: these tests say nothing about
results or times.  The topology is described inside a fixture (never at import), and
the persistent compilation cache is off for this file, since a compile for a
described chip can be written to it but never read back.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.inference.backends.bodies import make_step_bodies
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.fused import residual_rmsnorm, rmsnorm_matmul
from repro.kvcache import PagedKVCache
from repro.models import init_params

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


# ------------------------------------------------------ served step programs
# smollm-chat's serving shapes: 64 slots, 8192 pool blocks of 16 tokens,
# 2048-token context, 512-token prefill chunks
SLOTS, POOL_BLOCKS, CHUNK = 64, 8192, 512
# what the compiler may keep beside arguments and output, per program
TEMP_LIMIT = {"paged_decode": 0.4e9, "paged_prefill": 0.1e9}

_INSTR = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def _pool_layer_moves(hlo: str, layer: tuple) -> list:
    """Instructions that move one layer of a pool leaf: a ``copy`` whose
    shape, size-1 axes aside, is ``layer`` (pages first, in any split of the
    row), or a ``dynamic-update-slice`` writing such an operand.  The
    gathered view of every row's table may hold as many elements, but it is
    not pages-major."""
    def is_layer(dims):
        dims = [d for d in dims if d != 1]
        return dims[:1] == [layer[0]] and math.prod(dims) == math.prod(layer)

    shapes, found = {}, []
    for name, shape, op, args in _INSTR.findall(hlo):
        shapes[name] = [int(d) for d in shape.split(",") if d]
        if op == "copy" and is_layer(shapes[name]):
            found.append(name)
        elif op == "dynamic-update-slice":
            update = re.findall(r"%([\w.-]+)", args)[1]
            if is_layer(shapes.get(update, [])):
                found.append(name)
    return found


@pytest.fixture(scope="module")
def served_steps(one_chip):
    """The paged decode step and a full prefill chunk, compiled as
    ``LocalBackend`` jits them, with the pool leaves' shape."""
    kv = PagedKVCache(CFG, num_blocks=POOL_BLOCKS, block_size=BLOCK,
                      max_len=MAX_LEN)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                      CFG)))
    pages = place(jax.eval_shape(kv.make_pages))
    nb, i32 = kv.nb_per_slot, jnp.int32
    args = {"paged_decode": ((SLOTS, 1), (SLOTS,), (SLOTS, nb)),
            "paged_prefill": ((1, CHUNK), (nb,), ())}
    bodies = make_step_bodies(CFG)
    compiled = {
        name: jax.jit(getattr(bodies, name)).lower(
            params, pages, *(_sds(s, i32, one_chip) for s in shapes)
        ).compile()
        for name, shapes in args.items()}
    return compiled, jax.tree.leaves(pages)[0].shape


@pytest.mark.parametrize("program", ["paged_decode", "paged_prefill"])
def test_step_keeps_pool_in_place(served_steps, program):
    """The layer loop reads and writes the pool where it lies: no copy of a
    pool layer, and temporaries far below one layer of the pool."""
    compiled, leaf = served_steps
    c = compiled[program]
    layer = tuple(d for d in leaf[1:] if d != 1)       # (pages, row)
    assert _pool_layer_moves(c.as_text(), layer) == []
    assert c.memory_analysis().temp_size_in_bytes <= TEMP_LIMIT[program]
