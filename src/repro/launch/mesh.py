"""Device meshes: the one constructor every caller uses, plus the
production TPU v5e layouts.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run pins the host-device count *before* any jax
initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`.

    JAX's default axis type is `Explicit`, under which the vmapped worker
    axis of a local step is refused ("Mapped away dimension of inputs passed
    to vmap should be sharded the same").  The runtime places its state with
    NamedShardings and lets GSPMD propagate the rest, which is the `Auto`
    contract."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2, *, pods: int = 0):
    """Small host-device mesh for tests (requires matching
    xla_force_host_platform_device_count)."""
    if pods:
        return make_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
