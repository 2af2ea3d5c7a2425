"""Mesh construction.

FUNCTIONS (not module constants) so importing this module never touches
jax device state.  The dry-run sets XLA_FLAGS for 512 placeholder host
devices *before* any jax import (see dryrun.py).

Every mesh has ``AxisType.Auto`` axes: the model code places arrays with
sharding hints and lets the partitioner propagate them (``jax.make_mesh``
defaults to ``Explicit`` axes, under which e.g. the vocab-sharded
embedding gather must name its output sharding).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """A device mesh over the visible devices with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape))


def abstract_mesh(axes: Sequence[Tuple[str, int]]) -> AbstractMesh:
    """Device-free mesh from ((axis_name, size), ...) pairs, Auto axes."""
    names = tuple(n for n, _ in axes)
    sizes = tuple(s for _, s in axes)
    return AbstractMesh(sizes, names, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=data*model)."""
    return make_mesh((data, model), ("data", "model"))


def make_serve_mesh(tp: int, data: int = 1):
    """Serving mesh: ``model`` is the tensor-parallel axis the serve-mode
    ``ShardingPolicy`` head-shards attention/KV over; ``data`` replicates
    (or batch-shards) the engine across the remaining devices.  Used by
    ``launch/serve.py --tp N`` and the sharded-serve tests."""
    need = tp * data
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"--tp {tp} (x data {data}) needs {need} devices, have {have}; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}")
    return make_mesh((data, tp), ("data", "model"))
