"""Device placement for the serving tier: mesh + logical-axis rules.

The engine is shape-polymorphic and sharding-oblivious; placement is the
tier's job. A :class:`ServePlacement` pairs a mesh with the repo's
logical-axis :class:`~repro.distributed.sharding.Rules` table and pins the
batched request operands before submit: the query axis of ``X [Q, D, F]``
and ``mask [Q, D]`` carries the logical ``"batch"`` axis (data parallel —
queries are independent), documents and features stay replicated per
device. GSPMD then partitions the whole compiled step along Q; no engine
code changes.

``single_device()`` (``mesh=None``) is the fast path: ``put`` is the
identity, so serving on one device is *bit-exact* with the pre-placement
code — there is no "sharded but degenerate" overhead to pay, and the
1-device mesh path (:func:`local`) is itself a numerical no-op the tests
cross-check against it.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.distributed.sharding import Rules, single_pod_rules
from repro.kernels.ops import ROW_AXIS
from repro.launch.mesh import make_local_mesh, make_mesh


@dataclasses.dataclass(frozen=True)
class ServePlacement:
    """Where serving batches live. ``mesh=None`` → plain single device."""

    mesh: Mesh | None = None
    rules: Rules | None = None

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def _batch_shards(self) -> int:
        """How many ways the logical "batch" axis is split on this mesh."""
        phys = self.rules.physical("batch")
        if phys is None:
            return 1
        axes = (phys,) if isinstance(phys, str) else phys
        n = 1
        for ax in axes:
            n *= self.mesh.shape[ax]
        return n

    def scope(self) -> contextlib.AbstractContextManager:
        """Context the engine step is traced and run in: the mesh (row
        sharding of the forest launches), or nothing for one device."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def put(
        self, X: jax.Array, mask: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """Pin ``X [Q, D, F]`` / ``mask [Q, D]`` to the mesh, query-axis
        data-parallel. Identity when ``mesh is None``. A Q not divisible
        by the batch-axis shard count falls back to replication (the
        batcher's power-of-two query buckets make this the exception, not
        the rule — but a stray shape must degrade, never crash)."""
        if self.mesh is None:
            return X, mask
        if X.shape[0] % max(self._batch_shards(), 1) == 0:
            x_spec = self.rules.resolve("batch", None, None)
            m_spec = self.rules.resolve("batch", None)
        else:
            x_spec = m_spec = PartitionSpec()
        return (
            jax.device_put(X, NamedSharding(self.mesh, x_spec)),
            jax.device_put(mask, NamedSharding(self.mesh, m_spec)),
        )


def single_device() -> ServePlacement:
    """No mesh at all — today's path, byte for byte."""
    return ServePlacement(mesh=None, rules=None)


def local() -> ServePlacement:
    """1×1 mesh over the local device with the production rules table:
    exercises the full placement machinery with nothing actually split."""
    return ServePlacement(mesh=make_local_mesh(), rules=single_pod_rules())


def data_parallel(n_devices: int | None = None) -> ServePlacement:
    """(n, 1) mesh over ("data", "model"): query axis split n ways."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    assert 1 <= n <= len(devs), (n, len(devs))
    mesh = make_mesh((n, 1), (ROW_AXIS, "model"), devices=devs[:n])
    return ServePlacement(mesh=mesh, rules=single_pod_rules())


def auto() -> ServePlacement:
    """Data-parallel over every visible device; plain single-device path
    when there is only one (keeps the 1-device case bit-exact)."""
    return data_parallel() if len(jax.devices()) > 1 else single_device()
