"""Production mesh definitions (TPU v5e pods).

Functions, not module-level constants — importing this module never touches
jax device state.  Every mesh in the repository is built by :func:`make_mesh`
with Auto axis types: the model code places arrays through
``with_sharding_constraint`` and GSPMD propagation, which Explicit axes
(``jax.make_mesh``'s default since jax 0.7) refuse."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke tests / examples): (data, model)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def mesh_for(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh of exactly ``prod(shape)`` devices from this process's device
    list.  This is the elastic-reshape seam: ``FTManager.viable_mesh`` picks
    a (shape, axes) rung off the ladder after worker loss, and the supervisor
    rebuilds the mesh from the devices that remain — fewer than the full
    host/pod set, which ``jax.make_mesh`` supports via ``devices=``."""
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(f"mesh {shape} needs {need} devices, host has "
                         f"{len(devs)}")
    return make_mesh(shape, axes, devices=devs[:need])


def chips(mesh) -> int:
    return mesh.devices.size
