"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only
``dryrun.py`` forces the 512-device placeholder platform).

Every mesh in the repo is built by :func:`make_mesh`.  Since jax 0.7,
``jax.make_mesh`` defaults to *Explicit* axes, under which un-annotated
gathers raise ``ShardingTypeError`` and a jit outside ``jax.set_mesh`` is
placed on one device; the substrate is written for GSPMD propagation, so
the helper pins every axis to ``AxisType.Auto``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_mesh_by_name"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_by_name(name: str):
    """'single' -> 16x16, 'multi' -> 2x16x16, 'AxB[xC]' -> custom."""
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    dims = tuple(int(x) for x in name.split("x"))
    axes = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[
        len(dims)
    ]
    return make_mesh(dims, axes)
