"""Instrumentation helpers shared by tests, benchmarks and ``chip_smoke.py``.

:func:`count_lowerings` counts jit lowerings (jaxpr → MLIR module) in a
block, on every thread — the served window of a warmed tier must show
zero. It listens on jax's public monitoring event for the lowering, so
lowerings on the batcher's worker thread count too.

:func:`mosaic_kernel_rows` reads a compiled program's Mosaic kernel
launches and the rows each is fed: proof that the device runs compiled
kernels (not interpret mode) on its own share of the batch.

:func:`count_host_transfers` is the device-residency guard: it counts
device→host materializations of jax arrays while a block runs, split into
*explicit* reads (``jax.device_get`` — the sanctioned, fused stats read)
and *implicit* syncs (``float()`` / ``int()`` / ``bool()`` / ``np.asarray``
on a device array — the accidental kind that stalls the serving hot path).

Why not ``jax.transfer_guard``? On the CPU backend (this container)
device and host share memory, so jax's own guard never fires — it would
make the zero-transfer contract vacuously true. Instead we hook
``ArrayImpl._value``, the single Python chokepoint every host
materialization funnels through (``__array__``, ``__float__``,
``__int__``, ``__bool__``, ``device_get`` all read it), and attribute
hits inside a ``jax.device_get`` call to the explicit bucket.

Known blind spot: a raw buffer-protocol read (``memoryview``-style C
access that numpy *can* take on CPU zero-copy arrays) bypasses
``_value``. Serving code never does that; the guard is aimed at the
Python-level sync vectors that actually appear in hot paths.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading

import jax
from jax._src import array as _jax_array

#: jax.monitoring duration event recorded once per jaxpr → MLIR lowering.
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@contextlib.contextmanager
def count_lowerings():
    """Count jit lowerings while the ``with`` block runs, on any thread.

    Yields a callable returning the count so far (read it inside or after
    the block)::

        with count_lowerings() as lowerings:
            serve()
        assert lowerings() == 0
    """
    lock = threading.Lock()
    count = [0]

    def listener(event: str, duration: float, **kwargs: object) -> None:
        if event == LOWERING_EVENT:
            with lock:
                count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield lambda: count[0]
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
_ROWS = re.compile(r"=\s*f32\[(\d+),\d+\]")


def mosaic_kernel_rows(hlo_text: str) -> list[int]:
    """Row count of each Mosaic kernel launch (``tpu_custom_call``) in a
    compiled program's HLO text — per device, in a partitioned program.
    Every forest launch returns ``f32[rows, segments]``."""
    rows = []
    for line in hlo_text.splitlines():
        if _MOSAIC_CALL in line:
            m = _ROWS.search(line)
            if m is None:
                raise ValueError(f"unparsed kernel launch: {line[:200]}")
            rows.append(int(m.group(1)))
    return rows


@dataclasses.dataclass
class TransferCounts:
    """Mutable tally yielded by :func:`count_host_transfers`."""

    explicit_gets: int = 0   # jax.device_get calls
    implicit_syncs: int = 0  # host materializations outside device_get


@contextlib.contextmanager
def count_host_transfers():
    """Count device→host transfers in the ``with`` block.

    Yields a :class:`TransferCounts`; read it after the block. Not
    re-entrant and patches process-global hooks — test-scope only, never
    in serving code.
    """
    counts = TransferCounts()
    local = threading.local()

    real_get = jax.device_get
    real_value = _jax_array.ArrayImpl._value

    def counting_get(*args, **kwargs):
        counts.explicit_gets += 1
        local.in_get = True
        try:
            return real_get(*args, **kwargs)
        finally:
            local.in_get = False

    class CountingValue:
        def __get__(self, obj, objtype=None):
            if obj is not None and not getattr(local, "in_get", False):
                counts.implicit_syncs += 1
            return real_value.__get__(obj, objtype)

    jax.device_get = counting_get
    _jax_array.ArrayImpl._value = CountingValue()
    try:
        yield counts
    finally:
        jax.device_get = real_get
        _jax_array.ArrayImpl._value = real_value
