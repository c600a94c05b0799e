"""Pluggable execution backends for the serving engine.

The scheduler (``repro.inference.engine.ServeEngine``) is device-free;
everything that places tensors, builds meshes, or dispatches compiled
steps implements the ``ExecutionBackend`` protocol here:

  * ``LocalBackend``   — single device; jit or launch-plan dispatch
  * ``ShardedBackend`` — tensor-parallel shard_map over a device mesh

``make_backend`` picks by tensor-parallel degree.  New scale axes (DP
replicas, pipeline serving, speculative decoding) are new backends.
"""
from repro.inference.backends.base import (  # noqa: F401
    BackendInfo, CallAccount, ExecutionBackend,
)
from repro.inference.backends.local import LocalBackend  # noqa: F401


def make_backend(cfg, params, *, max_batch: int, max_len: int,
                 tp: int = 1, plan: str = "jit",
                 platform: str = "TPU-v5e", device=None):
    """Backend for a tensor-parallel degree: tp=1 local, tp>1 sharded.

    ``device`` pins a tp=1 backend to one device (default: the first).
    The sharded import is deferred so single-device serving never touches
    mesh/shard_map machinery (and its device-count validation).
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp == 1:
        return LocalBackend(cfg, params, max_batch=max_batch,
                            max_len=max_len, plan=plan, platform=platform,
                            device=device)
    if device is not None:
        raise ValueError(f"device= pins a tp=1 backend; tp={tp} spans "
                         "the first tp devices of its mesh")
    from repro.inference.backends.sharded import ShardedBackend
    return ShardedBackend(cfg, params, max_batch=max_batch,
                          max_len=max_len, tp=tp, plan=plan,
                          platform=platform)
