"""Mesh construction: the one place this repo calls ``jax.make_mesh``.

Functions (not module-level constants) so importing this module never
touches jax device state.

Every axis is ``AxisType.Auto``.  On the installed jax (0.9)
``jax.make_mesh`` defaults to Explicit axes (sharding in types), under
which the HSS stack's ``with_sharding_constraint`` pins and its node-axis
pair/unpair reshapes raise; the whole repo is written for propagated
(Auto) shardings.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).  Multi-pod:
(pod=2, data=16, model=16) = 512 chips; the "pod" axis composes with "data"
for batch/FSDP sharding (DCI collectives), "model" stays intra-pod (ICI).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Sequence | None = None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_data_mesh(devices: Sequence | None = None) -> Mesh:
    """1-D ("data",) mesh over ``devices`` (default: every local device) —
    the node/sample axis the HSS engine shards over."""
    devices = list(jax.devices() if devices is None else devices)
    return make_mesh((len(devices),), ("data",), devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
