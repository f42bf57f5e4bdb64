"""Mesh construction (production + elastic).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state -- required for the dry-run's
device-count override to work.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto(n: int):
    # jax.make_mesh defaults to Explicit axes; the sharding helpers
    # constrain with bare PartitionSpecs, which need Auto axes
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_elastic_mesh(model_parallel: int = 1,
                      devices: Optional[Sequence] = None):
    """Build a (data, model) mesh from whatever devices exist, degrading
    model-parallel size to the largest divisor of the device count --
    the elastic-scaling entry point (a failed host shrinks the mesh and
    training resumes from the last checkpoint)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    mp = max(d for d in range(1, model_parallel + 1) if n % d == 0)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         devices=devices, axis_types=_auto(2))


def validate_mesh(mesh, global_batch: int) -> None:
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    if global_batch % dp and global_batch != 1:
        raise ValueError(
            f"global_batch {global_batch} not divisible by data-parallel "
            f"size {dp} of mesh {dict(mesh.shape)}")
    # global_batch == 1 (long-context decode): batch replicates; the cache
    # sequence dim shards over dp instead (see sharding.cache_specs)
