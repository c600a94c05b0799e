"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Every constructor validates the requested axis
sizes against ``jax.device_count()`` first — an undersized device pool
fails with an actionable message (how to simulate host devices on CPU)
instead of the XLA shape error ``jax.make_mesh`` would raise.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def require_device_count(n: int, *, what: str = "mesh") -> None:
    """Fail fast — and actionably — when a mesh/axis request exceeds the
    visible device count.

    Without this, ``jax.make_mesh`` surfaces the shortfall as an XLA
    reshape error deep inside device assignment.  Raised here instead,
    with the fix inline: on the CPU backend devices are simulated, so the
    remedy is an env var, not new hardware.
    """
    if n < 1:
        raise ValueError(f"{what} needs a positive device count, got {n}")
    have = jax.device_count()
    if n > have:
        backend = jax.default_backend()
        hint = (
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(before importing jax) to simulate {n} host devices"
            if backend == "cpu" else
            f"run on a host with >= {n} {backend} devices")
        raise ValueError(
            f"{what} needs {n} devices but jax.device_count() == {have} "
            f"on backend {backend!r}; {hint}")


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``NamedSharding``/``with_sharding_constraint`` and leave the rest
    to the partitioner, which Explicit axes (the default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    require_device_count(
        math.prod(shape),
        what=f"production mesh {dict(zip(axes, shape))}")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (tests / local runs)."""
    for name, size in (("data", data), ("model", model)):
        if size < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {size}")
    if pod < 0:
        raise ValueError(f"mesh axis 'pod' must be >= 0, got {pod}")
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    require_device_count(math.prod(shape),
                         what=f"host mesh {dict(zip(axes, shape))}")
    return _auto_mesh(shape, axes)


def make_fleet_mesh(replicas: int, tp: int = 1):
    """``(data=replicas, model=tp)`` grid for a serving replica fleet.

    The data axis indexes replicas (each serves whole requests), the
    model axis is each replica's tensor-parallel degree — the same two
    axes training uses, so a deployment can flip between the two without
    re-slicing its device pool.  Raises when the pool holds fewer than
    replicas*tp devices; ``ReplicaFleet(validate_mesh=True)`` calls it
    to refuse a fleet that would have to share devices.
    """
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    return make_host_mesh(data=replicas, model=tp)


def dp_axes_of(mesh) -> tuple[str, ...]:
    """Mesh axis names that carry data parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis_of(mesh) -> str:
    """Mesh axis name that carries tensor parallelism."""
    return "model"
