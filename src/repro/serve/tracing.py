"""Names of the serving path's host spans, in one place.

Host spans are :class:`jax.profiler.TraceAnnotation`: under a running
profiler each lands in the host plane of the trace, on the same clock as
the device ops; with none running it costs about a microsecond. The
batcher worker opens them, one thread, nested as::

    lear.wait                       waiting for a bucket to be ready
    lear.flush                      one flush (bucket, queries, trigger, seq)
        lear.pack                   expiry check, padded block, row copies
        lear.put                    host→device puts of the block and mask
        lear.step                   RankingService.rank_batch
            lear.step.dispatch      placement, engine config, compiled step
                                    (mask_words, leaf_gather)
            lear.step.post          what is left before the read: unpacking
            lear.step.read          the one jax.device_get
            lear.step.adapt         survivor peaks, EMA and stats
        lear.scatter                per-request top-k and set_result

``lear.flush`` and ``lear.step.dispatch`` carry metadata; the dispatch's
names the ranker's exit-leaf encoding: ``mask_words``, the uint32 words of
a node's false-leaf mask (``ceil(n_leaves / 32)``), and ``leaf_gather``,
the kernel's leaf-value path. The device scopes of the compiled
step (``lear/head``, ``lear/features``, ``lear/classifier``,
``lear/compaction``, ``lear/tail``) are named where the step is built,
:mod:`repro.core.cascade` (``SCOPE_*``), since the engine imports nothing
from the serving layer.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

WAIT = "lear.wait"
FLUSH = "lear.flush"
PACK = "lear.pack"
PUT = "lear.put"
STEP = "lear.step"
DISPATCH = "lear.step.dispatch"
POST = "lear.step.post"
READ = "lear.step.read"
ADAPT = "lear.step.adapt"
SCATTER = "lear.scatter"

#: Every host span, outermost first.
SPANS = (WAIT, FLUSH, PACK, PUT, STEP, DISPATCH, POST, READ, ADAPT,
         SCATTER)

#: ``span(name, **meta)``: a host span; ``meta`` becomes the event's stats.
span = TraceAnnotation
