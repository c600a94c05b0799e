"""Weights from the seed, drawn by the benchmark and not by the program.

Each leaf of the program's parameter tree is drawn from a key folded from
the seed and the leaf's path; a leaf stacked over layers draws layer ``l``
from that key folded with ``l``.  The program gets the whole tree from one
jitted call, built on the device in the dtype it serves; the reference
draws one layer at a time with the same function and gets the same values.

    matrices, biases   0.02 * N(0, 1)
    wq and wk          sqrt(QK_GAIN / d) * N(0, 1), QK_GAIN = 1: q and k
                       have unit entries and the attention scores a
                       standard deviation of about 1, so the output
                       depends on which keys the context holds.  At 0.02
                       attention is near uniform and a step that loses
                       the cache's new entries changes almost no logit;
                       at a gain of 3, 32 layers amplify bfloat16 rounding
                       until it reads like a fault (``control.py
                       --witness`` reads that with the reference alone).
    norm scales        1 + 0.1 * N(0, 1)
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACK = "blocks"        # leaves under this key carry a leading layer axis
QK_GAIN = 1.0


def seed_key(seed: int):
    """A threefry key from any non-negative whole number (64 bits)."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, path: str, shape, dtype):
    """One layer's value of leaf ``path`` (without the layer axis)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        x = 1.0 + 0.1 * x
    elif path.endswith(("/wq", "/wk")):
        x = (QK_GAIN / shape[0]) ** 0.5 * x
    else:
        x = 0.02 * x
    return x.astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def layer_leaf(key, path: str, shape, dtype, layer):
    """Layer ``layer`` of a stacked leaf of full shape ``shape``."""
    return draw(jax.random.fold_in(_leaf_key(key, path), layer), path,
                tuple(shape[1:]), dtype)


def build(key, shapes):
    """The whole tree for ``shapes`` (a pytree of ShapeDtypeStruct)."""
    def one(path, s):
        p = path_str(path)
        if p.startswith(STACK + "/"):
            return jax.vmap(lambda layer: layer_leaf(key, p, s.shape, s.dtype,
                                                     layer))(
                jnp.arange(s.shape[0]))
        return draw(_leaf_key(key, p), p, s.shape, s.dtype)
    return jax.tree_util.tree_map_with_path(one, shapes)


def program_params(cfg, seed: int, shardings=None):
    """The served parameters of ``cfg`` (a ``repro`` ModelConfig) from the
    seed, built on the device by one jitted call, straight into
    ``shardings`` when given (else on the default device)."""
    from repro.models import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.jit(lambda k: build(k, shapes),
                   out_shardings=shardings)(seed_key(seed))
