"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Single pod = 16x16 = 256 chips (v5e pod);
multi-pod adds a leading "pod" axis (2 pods = 512 chips).

Axis roles:
  "pod"   — sub-cluster replication (MGBC fr; LM/GNN/recsys pure DP)
  "data"  — batch / MGBC grid rows (R)
  "model" — tensor/expert parallel / MGBC grid columns (C)

Every mesh in tests, benchmarks, examples and launchers goes through
:func:`make_mesh`, which states the axis types explicitly: all axes are
``Auto`` (the shard_map bodies manage their own collectives).
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_bench_mesh"]


def make_mesh(
    shape: Sequence[int], axis_names: Sequence[str], *, devices=None
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(shape),
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_bench_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary meshes for scaling benchmarks (fr/fd sweeps)."""
    return make_mesh(shape, axes)
