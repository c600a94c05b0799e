"""Fused Pallas kernels for the decode hot path.

Each subpackage mirrors the top-level kernel layout — ``kernel.py`` is the
hand-tiled Pallas TPU kernel, ``ops.py`` the jitted shape-polymorphic
wrapper, ``ref.py`` the pure-jnp oracle.  The rule registry
(``repro.runtime.rules``) substitutes these kernels into launch plans;
off the TPU the plan executor runs them in interpret mode.

residual_rmsnorm  — residual add + RMSNorm (+ optional plain-norm form):
                    the 9/10-eqn window at every decoder block boundary
rmsnorm_matmul    — RMSNorm + projection matmul: the norm that feeds the
                    qkv/MLP dot_general, one VMEM round trip for both
"""

from repro.kernels.fused.residual_rmsnorm.ops import (  # noqa: F401
    residual_rmsnorm,
)
from repro.kernels.fused.rmsnorm_matmul.ops import rmsnorm_matmul  # noqa: F401
