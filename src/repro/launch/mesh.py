"""Production mesh construction.

Single pod: 16 × 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 × 16 × 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is an additional data-parallel dimension with slower (DCI)
links; logical rules place only batch-like axes (and the widest expert
dimension) on it.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; callers (dryrun.py) must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import.
"""

from __future__ import annotations

from collections.abc import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    devices: Sequence[jax.Device] | None = None,
) -> jax.sharding.Mesh:
    """Mesh with ``Auto`` axes: the SPMD partitioner shards the program
    from its operands' shardings (jax's default axis type is ``Explicit``,
    under which a ``pallas_call`` outside ``shard_map`` is refused)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh() -> jax.sharding.Mesh:
    """1×1 mesh over the single local device — CPU tests of the mesh path."""
    return make_mesh((1, 1), ("data", "model"))
