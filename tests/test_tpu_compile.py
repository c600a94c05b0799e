"""Described-chip compiles of the served kernels at smollm-360m widths
(and the paged decode kernel at one device's slice of codeqwen1.5-7b at
tp=4).

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
from repro.kernels.decode_attention.layout import row_shape
from repro.kernels.decode_attention.ops import (paged_decode_attention,
                                                paged_decode_attention_quant)
from repro.kernels.fused import residual_rmsnorm, rmsnorm_matmul
from repro.kvcache import PagedKVCache
from repro.layers import attention
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


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# (rows, query heads, KV heads per device, head dim, pages, layers): the
# smollm-chat cell, the same at 128 slots, and one device's slice of
# codeqwen1.5-7b at tp=4 (8 slots x 2048 tokens, 32/4 heads of 128 over
# four devices)
POOL_KERNEL_SHAPES = {
    "smollm-chat": (64, 15, 5, 64, 8192, 32),
    "smollm-128-slots": (128, 15, 5, 64, 8192, 32),
    "codeqwen-tp4-slice": (8, 8, 1, 128, 1024, 32),
}


@pytest.mark.parametrize("shape", list(POOL_KERNEL_SHAPES))
def test_paged_decode_pool_kernel_compiles(one_chip, shape):
    b, hq, hkv, hd, n_pages, n_layers = POOL_KERNEL_SHAPES[shape]
    pool = _sds((n_layers, 1, n_pages) + row_shape(BLOCK, hkv, hd),
                jnp.bfloat16, one_chip)
    rows = _sds((b,), jnp.int32, one_chip)
    compiled = paged_decode_attention.lower(
        _sds((b, hq, hd), jnp.bfloat16, one_chip), pool, pool,
        _sds((), jnp.int32, one_chip),
        _sds((b, MAX_LEN // BLOCK), jnp.int32, one_chip), rows, rows,
        scale=hd ** -0.5, kv_heads=hkv, interpret=False).compile()
    assert _has_kernel(compiled)


def test_paged_decode_int8_compiles(one_chip):
    hq, hkv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    pages = _sds((PAGES, BLOCK, hkv, hd), jnp.int8, one_chip)
    scales = _sds((PAGES, BLOCK, hkv), jnp.float32, one_chip)
    compiled = paged_decode_attention_quant.lower(
        _sds((BATCH, hq, hd), jnp.bfloat16, one_chip), pages, pages, scales,
        scales, _sds((BATCH, MAX_LEN // BLOCK), jnp.int32, one_chip),
        _sds((BATCH,), jnp.int32, one_chip), scale=hd ** -0.5,
        interpret=False).compile()
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
TEMP_LIMIT = {"paged_decode": 0.15e9, "paged_prefill": 0.1e9}

_INSTR = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def _pool_moves(hlo: str, leaf: tuple) -> dict:
    """Instructions that move a pool leaf, size-1 axes aside: ``"whole"``,
    each ``copy`` of the whole stacked leaf; ``"layer"``, each ``copy`` of
    one layer of it (pages first, in any split of the row) and each
    ``dynamic-update-slice`` writing a layer or the whole leaf.  The
    gathered view of every row's table may hold as many elements as a
    layer, but it is not pages-major."""
    leaf = [d for d in leaf if d != 1]
    layer = leaf[1:]

    def kind(dims):
        dims = [d for d in dims if d != 1]
        if dims == leaf:
            return "whole"
        if dims[:1] == layer[:1] and math.prod(dims) == math.prod(layer):
            return "layer"
        return None

    shapes, found = {}, {"whole": [], "layer": []}
    for name, shape, op, args in _INSTR.findall(hlo):
        shapes[name] = [int(d) for d in shape.split(",") if d]
        if op == "copy" and kind(shapes[name]):
            found[kind(shapes[name])].append(name)
        elif op == "dynamic-update-slice":
            update = re.findall(r"%([\w.-]+)", args)[1]
            if kind(shapes.get(update, [])):
                found["layer"].append(name)
    return found


def _table_gathers(hlo: str, elems: int) -> list:
    """``gather`` instructions whose output holds at least ``elems``
    elements: every row's whole table of pages."""
    return [name for name, shape, op, _ in _INSTR.findall(hlo)
            if op == "gather"
            and math.prod(int(d) for d in shape.split(",") if d) >= elems]


@pytest.fixture(scope="module")
def served_steps(one_chip):
    """The paged decode step and a full prefill chunk compiled as
    ``LocalBackend`` jits them, and again with the pool donated, with the
    pool leaves' shape.  Pallas compiles for the described chip here, so
    the program takes the kernel path it takes on the TPU."""
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
    compiled = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "pallas_interpret", lambda: False)
        for name, shapes in args.items():
            for donate in ((), (1,)):
                compiled[name, bool(donate)] = jax.jit(
                    getattr(bodies, name), donate_argnums=donate).lower(
                    params, pages, *(_sds(s, i32, one_chip) for s in shapes)
                ).compile()
    leaves = jax.tree.leaves(pages)
    return compiled, leaves[0].shape, nb, len(leaves)


@pytest.mark.parametrize("program", ["paged_decode", "paged_prefill"])
def test_step_keeps_pool_in_place(served_steps, program):
    """The layer loop reads and writes the pool where it lies.  As served
    (undonated) the program copies each pool leaf once, whole, into its
    output and moves no layer of it; with the pool donated not even that
    copy is left.  Temporaries stay far below one layer of the pool."""
    compiled, leaf, _, n_leaves = served_steps
    served = _pool_moves(compiled[program, False].as_text(), leaf)
    assert served["layer"] == []
    assert len(served["whole"]) == n_leaves
    donated = _pool_moves(compiled[program, True].as_text(), leaf)
    assert donated == {"whole": [], "layer": []}
    temp = compiled[program, False].memory_analysis().temp_size_in_bytes
    assert temp <= TEMP_LIMIT[program]


def test_decode_step_reads_pages_in_one_kernel(served_steps):
    """The served decode step runs the paged kernel under the ``attn``
    scope, where ``attention_roofline`` counts its time, and gathers no
    row's whole table."""
    compiled, leaf, nb, _ = served_steps
    hlo = compiled["paged_decode", False].as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("/attn/" in line for line in kernels)
    assert _table_gathers(hlo, SLOTS * nb * math.prod(leaf[3:])) == []
