"""Mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes. ``jax.make_mesh``
defaults to *Explicit* axes, under which ``with_sharding_constraint``
(:func:`repro.distributed.sharding.shard`) and the fleet's ``shard_map``
reject the logical-axis specs this repo resolves at trace time.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], names: tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (1 device on CPU) — for smoke/examples."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
