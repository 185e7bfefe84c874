"""Mesh construction: the one place the repo builds a device mesh.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.

Every axis is `AxisType.Auto`.  `jax.make_mesh` gives Explicit axes
unless told otherwise, and an Explicit axis puts the sharding into the
type of every array placed on it: the deploy's per-tile health
reductions (`obs.health.tile_reduce`) then fail on column-sharded
statistics with a `ShardingTypeError`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` over the visible devices, every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the cross-DCI "pod" axis
    (2 pods = 512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_debug_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small mesh for CI-scale sharding tests (requires
    xla_force_host_platform_device_count >= n_data*n_model*max(pods,1))."""
    if pods:
        return make_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
