"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.  Single-pod: 16 x 16 = 256 chips (data, model);
multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model) — data-parallel
replicas across pods, tensor/expert parallelism within a pod (ICI), pod
axis crossing DCI.

Every axis is ``AxisType.Auto``: the model code places arrays with
``with_sharding_constraint`` and lets the compiler propagate the rest,
which an Explicit axis (``jax.make_mesh``'s default) refuses.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_cpu_mesh", "activate_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (1 real device unless XLA_FLAGS says more)."""
    return make_mesh((data, model), ("data", "model"))


def activate_mesh(mesh):
    """Context manager making `mesh` the ambient mesh for jit/sharding.
    All launch entry points and mesh-dependent tests route through here."""
    return jax.set_mesh(mesh)
