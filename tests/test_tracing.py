"""The serving path's own spans and counters.

- the batcher's counters of queue wait and document slots, exact on a
  virtual clock for a scripted sequence of submits;
- the span tree a real tier writes into a ``jax.profiler`` trace on the
  worker thread, read back with ``ProfileData``;
- the service's ``tail_rows`` beside ``docs_continued`` for a known
  bucket, and the rows a launch scores once padded to its blocks;
- the service's ``doc_tree_slots`` against a count worked out by hand
  from the bucket's rows and each launch's padded tree blocks.
"""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from tests.faults import FakeClock, FakeService
from repro.core.lear import LearClassifier
from repro.forest.ensemble import random_ensemble
from repro.kernels import ops
from repro.serve import tracing
from repro.serve.batching import (
    BatcherHooks,
    BucketPolicy,
    ContinuousBatcher,
)
from repro.serve.errors import DeadlineExceeded
from repro.serve.ranking_service import RankingService, ServiceConfig
from repro.serve.tier import ServingTier, TierConfig

F = 12
TIMEOUT_S = 30.0


def _rows(rng, n):
    return rng.normal(size=(n, F)).astype(np.float32)


def _results(futs):
    return [f.result(timeout=TIMEOUT_S) for f in futs]


def test_batcher_counts_queue_wait_and_document_slots():
    """A full flush of four queries in doc bucket 16, then a deadline flush
    of three in bucket 64 padded to four; virtual time moves only here."""
    clock = FakeClock(start=1000.0)
    policy = BucketPolicy(max_queries=4, max_wait_ms=2.0, min_docs=8)
    b = ContinuousBatcher(FakeService(), F, policy, clock=clock)
    rng = np.random.default_rng(0)
    b.start()
    try:
        futs = [b.submit(_rows(rng, 10))]           # t = 0
        clock.advance(0.0005)
        futs.append(b.submit(_rows(rng, 16)))       # t = 0.5 ms
        clock.advance(0.0005)
        futs.append(b.submit(_rows(rng, 9)))        # t = 1.0 ms
        clock.advance(0.0005)
        futs.append(b.submit(_rows(rng, 12)))       # t = 1.5 ms: full
        _results(futs)
        futs = [b.submit(_rows(rng, 40))]           # t = 1.5 ms
        clock.advance(0.001)
        futs += [b.submit(_rows(rng, 33)), b.submit(_rows(rng, 64))]
        clock.advance(0.0015)                       # t = 4.0 ms: the first
        _results(futs)                              # is 2.5 ms old
    finally:
        b.stop()
    s = b.stats
    assert (s.flushes_full, s.flushes_deadline, s.flushes_drain) == (1, 1, 0)
    assert s.completed == 7 and s.padded_query_slots == 1
    assert s.queue_waited == 7
    # Flush 1 starts at 1.5 ms: 1.5 + 1.0 + 0.5 + 0; flush 2 at 4.0 ms:
    # 2.5 + 1.5 + 1.5.
    assert s.queue_wait_s == pytest.approx(0.003 + 0.0055, abs=1e-9)
    # 4 x 16 slots hold 10 + 16 + 9 + 12 = 47 documents; 4 x 64 hold
    # 40 + 33 + 64 = 137.
    assert s.doc_slots == 64 + 256
    assert s.padded_doc_slots == (64 - 47) + (256 - 137)
    h = b.health()
    assert h["queue_wait_ms"] == pytest.approx(1e3 * 0.0085 / 7, abs=1e-6)
    assert "p50_ms" not in h and "p99_ms" not in h


def test_an_expired_request_counts_no_queue_wait():
    """Of two requests flushed together, the one whose deadline passed
    before the flush started is expired, not handed to the engine."""
    clock = FakeClock(start=50.0)
    hooks = BatcherHooks(on_flush=lambda db, n: clock.advance(0.010))
    b = ContinuousBatcher(FakeService(), F, BucketPolicy(max_queries=4),
                          clock=clock, hooks=hooks)
    rng = np.random.default_rng(1)
    b.start()
    try:
        late = b.submit(_rows(rng, 8), deadline_ms=10.0)
        kept = b.submit(_rows(rng, 8))
        clock.advance(0.002)          # deadline flush; the hook adds 10 ms
        kept.result(timeout=TIMEOUT_S)
        with pytest.raises(DeadlineExceeded):
            late.result(timeout=TIMEOUT_S)
    finally:
        b.stop()
    s = b.stats
    assert (s.expired_deadline, s.completed, s.queue_waited) == (1, 1, 1)
    assert s.queue_wait_s == pytest.approx(0.012, abs=1e-9)
    assert (s.doc_slots, s.padded_doc_slots) == (8, 0)


def _service(sentinel=8, n_trees=64, depth=4):
    ens = random_ensemble(0, n_trees=n_trees, depth=depth, n_features=F)
    clf = LearClassifier(
        forest=random_ensemble(100, n_trees=10, depth=3, n_features=F + 4),
        sentinel=sentinel,
    )
    svc = RankingService(
        ens, clf, ServiceConfig(threshold=0.4, execution_mode="fused",
                                launch_overhead_trees=512.0),
    )
    # Continue ⇔ feature 0 positive: survivor counts are set by the data.
    gate = lambda p, m, features=None: m & (features[..., 0] > 0.0)
    svc.stage_strategies = [gate]
    return svc


@pytest.mark.parametrize(
    "case, sentinel, seeded, tail_rows",
    [
        # Warm-up seeds the peaks at Q·D: the tail scores all 128 rows.
        ("seeded", 8, True, 128),
        # Cold start: 0.4 × 128 × 1.25 = 64 rows.
        ("cold", 8, False, 64),
        # The sentinel is the last tree: no tail launch.
        ("no_tail", 64, True, 0),
    ],
)
def test_tail_rows_beside_docs_continued(case, sentinel, seeded, tail_rows):
    svc = _service(sentinel=sentinel)
    if seeded:
        svc.bucket_state(2, 64).peaks = [128]
    X = np.random.default_rng(2).normal(size=(2, 64, F)).astype(np.float32)
    X[..., 0] = -1.0
    X[0, :30, 0] = 1.0          # 30 of query 0's 40 documents continue
    X[1, :20, 0] = 1.0          # 20 of query 1's 64
    mask = np.zeros((2, 64), bool)
    mask[0, :40] = True
    mask[1, :] = True
    svc.rank_batch(jnp.asarray(X), jnp.asarray(mask))
    svc.rank_batch(jnp.asarray(X), jnp.asarray(mask))
    s = svc.stats
    assert s.batches == 2 and s.docs == 2 * 104
    assert s.docs_continued == 2 * 50
    assert s.tail_rows == 2 * tail_rows
    assert s.dispatch_s > 0 and s.post_s > 0 and s.read_s > 0


@pytest.mark.parametrize(
    "case, sentinel, seeded, depth, per_batch",
    [
        # 128 rows (one block of 128). Head: trees 0..8 padded to one
        # block of 16; classifier: 10 trees padded to 16; tail: trees
        # 8..64 padded to 64, on all 128 rows once warm-up seeds the peaks.
        ("seeded", 8, True, 4, 128 * 16 + 128 * 16 + 128 * 64),
        # Cold start: the tail scores 64 rows.
        ("cold", 8, False, 4, 128 * 16 + 128 * 16 + 64 * 64),
        # No tail: the head launch covers all 64 trees.
        ("no_tail", 64, True, 4, 128 * 64 + 128 * 16),
        # 256-leaf trees take blocks of 4 trees: the head's 8 trees fill
        # two blocks, the tail's 56 fill fourteen.
        ("depth8", 8, True, 8, 128 * 8 + 128 * 16 + 128 * 56),
    ],
)
def test_doc_tree_slots_from_launch_shapes(case, sentinel, seeded, depth,
                                           per_batch):
    svc = _service(sentinel=sentinel, depth=depth)
    if seeded:
        svc.bucket_state(2, 64).peaks = [128]
    X = np.random.default_rng(2).normal(size=(2, 64, F)).astype(np.float32)
    X[..., 0] = -1.0
    X[0, :30, 0] = 1.0
    mask = np.zeros((2, 64), bool)
    mask[0, :40] = True
    mask[1, :] = True
    svc.rank_batch(jnp.asarray(X), jnp.asarray(mask))
    svc.rank_batch(jnp.asarray(X), jnp.asarray(mask))
    assert svc.stats.doc_tree_slots == 2 * per_batch


def test_launch_rows_pads_as_the_launch_does():
    assert ops.launch_rows(600) == 768          # 3 blocks of 256
    assert ops.launch_rows(5) == 8              # the smallest block
    assert ops.launch_rows(2048) == 2048
    x, _ = ops._prep_x(jnp.zeros((600, 3)), ops.ENGINE_BLOCK_B)
    assert x.shape[0] == ops.launch_rows(600)
    with jax.sharding.use_abstract_mesh(AbstractMesh((4,), ("data",))):
        # 150 rows per device → one block of 256 each.
        assert ops.launch_rows(600) == 1024
        assert ops.launch_rows(5) == 32         # 2 rows → 8 per device


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    lines = []
    for line in plane.lines:
        events = [(e.name, e.start_ns, e.end_ns, {k: v for k, v in e.stats})
                  for e in line.events if e.name in tracing.SPANS]
        if events:
            lines.append(sorted(events, key=lambda e: (e[1], -e[2])))
    return lines


def _inside(outer, names, events):
    """The events named in ``names`` inside ``outer``, in start order."""
    _, a, b, _ = outer
    return [e for e in events if e[0] in names and a <= e[1] and e[2] <= b]


def test_worker_spans_nest_in_order(tmp_path):
    """Two flushes of one query each under the profiler: on the worker's
    line, each ``lear.flush`` holds pack, put, step and scatter in that
    order, the step holds its four phases, and a ``lear.wait`` lies
    between the flushes."""
    from jax.profiler import ProfileOptions

    svc = _service()
    tier = ServingTier(
        svc, F,
        TierConfig(doc_counts=(32,), warmup=True, persistent_cache=False),
        policy=BucketPolicy(max_queries=2, max_wait_ms=1.0),
    )
    tier.start()
    rng = np.random.default_rng(3)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for n in (20, 30):
                tier.submit(_rows(rng, n)).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        tier.stop()
    service = tier.stats()["service"]
    assert service["tail_rows"] == 2 * 32     # (1, 32) at Q·D, two batches
    assert service["dispatch_s"] > 0 and service["read_s"] > 0
    assert tier.health()["queue_wait_ms"] > 0
    lines = _host_events(str(tmp_path))
    assert len(lines) == 1, "spans on more than the worker's thread"
    events = lines[0]
    flushes = [e for e in events if e[0] == tracing.FLUSH]
    assert len(flushes) == 2
    for k, flush in enumerate(flushes):
        meta = flush[3]
        assert meta["bucket"] == 32 and meta["queries"] == 1
        assert meta["trigger"] == "deadline"
        children = _inside(
            flush, {tracing.PACK, tracing.PUT, tracing.STEP,
                    tracing.SCATTER}, events)
        assert [e[0] for e in children] == [
            tracing.PACK, tracing.PUT, tracing.STEP, tracing.SCATTER]
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
        step = children[2]
        phases = _inside(
            step, {tracing.DISPATCH, tracing.POST, tracing.READ,
                   tracing.ADAPT}, events)
        assert [e[0] for e in phases] == [
            tracing.DISPATCH, tracing.POST, tracing.READ, tracing.ADAPT]
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        # Besides lear.flush, only lear.step.dispatch carries metadata:
        # the ranker's mask words per node and leaf-gather path.
        assert phases[0][3] == {"mask_words": 1, "leaf_gather": "select"}
        assert all(not e[3] for e in children + phases[1:])
    assert flushes[1][3]["seq"] == flushes[0][3]["seq"] + 1
    waits = [e for e in events if e[0] == tracing.WAIT
             and flushes[0][2] <= e[1] and e[2] <= flushes[1][1]]
    assert len(waits) == 1
