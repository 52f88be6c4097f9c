"""Seeded traffic: document counts, arrival schedules and query features.

A mix (``bench/mixes/<name>.json``) is data only:

- ``loop``: ``"open"`` (Poisson arrivals at ``rate_qps``, each request
  timed from when it was due) or ``"closed"`` (``in_flight`` queries
  outstanding at all times, a new one sent as each completes);
- ``docs``: the distribution of candidate-list lengths,
  ``{"dist": "lognormal", "mean", "sigma", "min", "max"}`` (``mean`` of
  the unclipped distribution) or ``{"dist": "uniform", "min", "max"}``;
- ``max_queue_depth`` (optional): the tier's admission bound for this
  traffic, in place of ``BucketPolicy``'s default. An open loop sets it
  deep enough that the arrivals of a host standstill of some seconds
  queue, and count their wait, instead of being refused.

Every seed gets the same multiset of list lengths and of inter-arrival
gaps, in its own order: both are stratified quantiles of their
distribution, shuffled by the seed. So the seed changes which request
comes when and what its features are, not how much work a run holds.
Query features are N(0, 1) float32 rows of one pool drawn from the seed;
request ``i`` takes ``sizes[i]`` consecutive rows from its own offset.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

#: Rows in the feature pool: requests start at seeded offsets into it.
POOL_ROWS = 1 << 16
#: Closed loops cycle through this many stratified list lengths.
CLOSED_CYCLE = 8192


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def doc_counts(spec: dict, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` list lengths: stratified quantiles of ``spec``, shuffled."""
    u = _strata(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        sigma = float(spec["sigma"])
        mu = np.log(float(spec["mean"])) - sigma**2 / 2
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        sizes = np.rint(np.exp(mu + sigma * z))
    elif spec["dist"] == "uniform":
        sizes = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown doc-count distribution {spec['dist']!r}")
    return gen.permutation(np.clip(sizes, lo, hi).astype(np.int64))


def arrival_gaps(rate_qps: float, n: int,
                 gen: np.random.Generator) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate_qps``: stratified
    exponential quantiles, shuffled. Request ``i`` is due after the first
    ``i`` of them."""
    return gen.permutation(-np.log1p(-_strata(n)) / rate_qps)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The requests of one run, in the order they are sent."""

    sizes: np.ndarray        # [N] documents per request
    rows: np.ndarray         # [N] first pool row of each request
    due: np.ndarray | None   # [N] due offsets (s); None for a closed loop
    pool: np.ndarray         # [POOL_ROWS, F] float32

    def __len__(self) -> int:
        return len(self.sizes)

    def features(self, i: int) -> np.ndarray:
        """Request ``i``'s ``[n, F]`` features (wraps for a closed loop)."""
        i %= len(self.sizes)
        r = int(self.rows[i])
        return self.pool[r:r + int(self.sizes[i])]


def generate(mix: dict, n_features: int, seconds: float,
             seed: int) -> Traffic:
    """The run's traffic: for an open loop every request due in the
    ``seconds`` window, for a closed loop a cycle to draw from."""
    root = np.random.default_rng([int(seed) % 2**64, 10])
    g_sizes, g_gaps, g_pool, g_rows = root.spawn(4)
    if mix["loop"] == "open":
        n = max(1, round(float(mix["rate_qps"]) * seconds))
        gaps = arrival_gaps(float(mix["rate_qps"]), n, g_gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    elif mix["loop"] == "closed":
        n, due = CLOSED_CYCLE, None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sizes = doc_counts(mix["docs"], n, g_sizes)
    pool = g_pool.standard_normal((POOL_ROWS, n_features), dtype=np.float32)
    rows = g_rows.integers(0, POOL_ROWS - sizes + 1)
    return Traffic(sizes=sizes, rows=rows, due=due, pool=pool)


def doc_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest list the mix can send."""
    return int(mix["docs"]["min"]), int(mix["docs"]["max"])
