"""Pallas TPU kernels for the compute hot-spots, each with a jitted wrapper
(ops.py) and a pure-jnp oracle (ref.py).

Every wrapper compiles its kernel for the device unless the caller passes
``interpret=True``; ``pallas_interpret()`` is the one place that picks the
interpreter, and only off the TPU (CPU tests and runs).

flash_attention  — online-softmax VMEM tiles, GQA via K/V index_map,
                   causal/sliding-window/softcap (the paper's
                   domain-specific-fusion exemplar, TPU-native)
decode_attention — flash-decoding over a long KV cache (memory-bound)
rmsnorm          — fused residual+RMSNorm (a PS=1 chain, hand-fused)
rwkv6            — chunked WKV6 with data-dependent decay (log-space,
                   overflow-safe; MXU cumsum via triangular matmul)
"""
import jax

from repro.kernels.flash_attention.ops import flash_attention  # noqa: F401
from repro.kernels.decode_attention.ops import decode_attention  # noqa: F401
from repro.kernels.rmsnorm.ops import rmsnorm as fused_rmsnorm  # noqa: F401
from repro.kernels.rwkv6.ops import wkv6  # noqa: F401


def pallas_interpret() -> bool:
    """True where Pallas kernels must run in the interpreter: on every
    backend but the TPU.  On a TPU this is False, so a kernel compiles
    for the chip unless a caller asks for the interpreter explicitly."""
    return jax.default_backend() != "tpu"
