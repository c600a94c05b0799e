"""Compile each cell's step programs at their timed shapes for a described
TPU v5e, without a chip, and print what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py smollm-chat

For each cell: the paged decode step at the configuration's batch and the
largest prefill chunk, built from the engine's own step bodies
(``inference/backends/bodies.py``) as ``LocalBackend`` jits them for one
chip.  Prints one JSON line per program with
``memory_analysis()``'s bytes per device.  A compile that passes is not a
chip run: it says nothing of times or results.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def shapes(model: dict):
    import jax
    import jax.numpy as jnp
    from repro.kvcache import PagedKVCache
    from repro.models import init_params
    from chipbench.harness.cell import repro_config
    cfg = repro_config(model)
    sv = model["serving"]
    kv = PagedKVCache(cfg, num_blocks=sv["num_blocks"],
                      block_size=sv["block_size"], max_len=sv["max_len"],
                      dtype=cfg.cdtype)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    pages = jax.eval_shape(kv.make_pages)
    B, C, nb = sv["max_batch"], sv["prefill_chunk"], kv.nb_per_slot
    i32 = jnp.int32
    decode = (jax.ShapeDtypeStruct((B, 1), i32), jax.ShapeDtypeStruct((B,), i32),
              jax.ShapeDtypeStruct((B, nb), i32))
    chunk = (jax.ShapeDtypeStruct((1, C), i32), jax.ShapeDtypeStruct((nb,), i32),
             jax.ShapeDtypeStruct((), i32))
    return cfg, params, pages, decode, chunk


def compile_cell(model: dict, topo) -> list:
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.inference.backends.bodies import make_step_bodies
    cfg, params, pages, decode, chunk = shapes(model)
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    bodies = make_step_bodies(cfg)
    progs = {"paged_decode": (jax.jit(bodies.paged_decode), decode),
             "paged_prefill_chunk": (jax.jit(bodies.paged_prefill), chunk)}
    return [(name, fn.lower(place(params), place(pages),
                            *place(args)).compile())
            for name, (fn, args) in progs.items()]


def main(argv=None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    import jax
    from jax.experimental import topologies
    from chipbench.harness import spec
    jax.config.update("jax_enable_compilation_cache", False)
    bench = spec.load_benchmark(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in (argv if argv is not None else sys.argv[1:]):
        cell = spec.find_cell(bench, name)
        model = spec.load_config(cell["config"])
        for prog, c in compile_cell(model, topo):
            m = c.memory_analysis()
            print(json.dumps({
                "cell": name, "program": prog,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "generated_code_bytes": m.generated_code_size_in_bytes}),
                flush=True)


if __name__ == "__main__":
    main()
