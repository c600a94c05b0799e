"""Pipeline parallelism: GPipe-style microbatch schedule over a `pipe`
mesh axis, built from shard_map + collective_permute.

The layer stack is split into P stages (stage-major stacked params, like
the scan-over-layers layout).  A scan over `n_micro + P - 1` ticks drives
the classic pipeline diagram: stage 0 injects microbatch t at tick t,
activations hop stage->stage+1 via ppermute each tick, the last stage
emits microbatch t at tick t + P - 1.  Bubble fraction = (P-1)/(ticks).

This is the orthogonal third axis to DP/TP for 1000+ node scale-out:
mesh ("pipe", "data", "model") composes with everything else in
distributed/sharding.py (stage params are just a leading-dim shard).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, stage_params, x_micro, mesh,
                     axis: str = "pipe"):
    """Run a P-stage pipeline over microbatches.

    stage_fn: (params_for_one_stage, x) -> y       (same shape)
    stage_params: pytree with leading dim P (stage-major)
    x_micro: (n_micro, mb, ...) microbatched input
    Returns (n_micro, mb, ...) outputs (from the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_device(params_local, x_stream):
        # params_local: one stage's params (leading dim 1 squeezed)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(x_stream[0])

        def tick(buf, t):
            # stage 0 injects microbatch t (zeros once drained)
            idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(t < n_micro, x_stream[idx], zero)
            xin = jnp.where(stage == 0, inject, buf)
            y = stage_fn(params_local, xin)
            # shift activations one stage down the ring
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            nxt = jax.lax.ppermute(y, axis, perm)
            return nxt, y

        _, ys = jax.lax.scan(tick, zero, jnp.arange(ticks))
        return ys[None]                                    # (1, ticks, ...)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(axis),
        check_vma=False)
    ys = fn(stage_params, x_micro)                 # (P, ticks, mb, ...)
    # last stage emits microbatch t at tick t + P - 1; the result lives on
    # that stage's device, so name its (replicated) sharding explicitly —
    # an Explicit ``pipe`` axis cannot infer it from the indexing alone
    return ys.at[n_stages - 1, n_stages - 1:].get(
        out_sharding=NamedSharding(mesh, P()))


def reference_forward(stage_fn: Callable, stage_params, x_micro):
    """Sequential oracle: apply all stages to each microbatch in order."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_one(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(run_one)(x_micro)
