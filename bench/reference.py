"""Plain float64 reference of the LEAR serving path, and the per-request check.

The reference shares nothing with the program: it walks the complete
heap-ordered trees that :mod:`bench.models` drew from the seed (node ``n``
has children ``2n+1`` and ``2n+2``; ``x <= threshold`` goes left) and sums
leaf values in float64. For one request it gives, per document:

- the partial score after the sentinel's head trees and the full score;
- the four LEAR features built from the partial score (partial, rank with
  ties broken by index, min-max normalised partial, candidate count);
- the classifier's logit and the exit decision against the threshold.

The program scores in float32 and builds the LEAR features from its own
float32 partial scores, so a decision can differ from the reference only
where a classifier comparison on an augmented feature, or the logit
itself, sits within rounding of its threshold. Such a document is
*undecided*: either the partial or the full score is a right answer for it.
Everywhere else the served score must be the one the decision picks.

:func:`replay` with ``precision="bfloat16"`` is the control: the same
replay with features, thresholds and leaf values rounded to bfloat16 and
sums in float32, the step below the float32 the configurations state.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

#: An augmented feature or a logit this close to a classifier threshold may
#: fall either side of it in the program's float32 arithmetic: its
#: partial scores are float32 sums of up to a few thousand leaf values of
#: scale 0.1, a few 1e-6 off the float64 sum.
ROUNDING_MARGIN = 1e-4
#: Two partial scores this close may rank either way round in float32.
TIE_MARGIN = 1e-5
N_AUG = 4


@dataclasses.dataclass(frozen=True)
class Forest:
    """Complete binary trees of one depth in heap order."""

    feature: np.ndarray    # [T, 2**depth - 1] integer feature index
    threshold: np.ndarray  # [T, 2**depth - 1] float32
    leaf: np.ndarray       # [T, 2**depth] float32, left to right

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def depth(self) -> int:
        return int(np.log2(self.leaf.shape[1]))

    def trees(self, lo: int, hi: int) -> Forest:
        return Forest(self.feature[lo:hi], self.threshold[lo:hi],
                      self.leaf[lo:hi])

    def rounded(self, dtype: type) -> Forest:
        return Forest(self.feature, _round(self.threshold, dtype),
                      _round(self.leaf, dtype))


@dataclasses.dataclass(frozen=True)
class Model:
    """A ranking ensemble, its sentinel and the exit classifier over
    ``F + 4`` features; a document continues iff ``logit >= logit_threshold``."""

    ranker: Forest
    classifier: Forest
    sentinel: int
    logit_threshold: float


@dataclasses.dataclass(frozen=True)
class Replay:
    """The reference's view of one request's documents."""

    partial: np.ndarray    # [n] score after the head trees
    full: np.ndarray       # [n] score after every tree
    cont: np.ndarray       # [n] bool: the document continues
    undecided: np.ndarray  # [n] bool: rounding may decide either way

    def served(self) -> np.ndarray:
        """The scores this replay would serve (the control's answer)."""
        return np.where(self.cont, self.full, self.partial)


def _round(a: np.ndarray, dtype: type) -> np.ndarray:
    return a.astype(dtype).astype(np.float32)


def walk(forest: Forest, X: np.ndarray, record: bool = False):
    """Leaf index ``[B, T]`` of every document in every tree and, with
    ``record``, per level the feature tested, its value and the threshold
    (each ``[B, T]``)."""
    B, F = X.shape
    T, n_int = forest.feature.shape
    flat = np.ascontiguousarray(X).ravel()
    rows = (np.arange(B) * F)[:, None]
    trees = (np.arange(T) * n_int)[None, :]
    feature, threshold = forest.feature.ravel(), forest.threshold.ravel()
    node = np.zeros((B, T), np.int64)
    path = []
    for _ in range(forest.depth):
        at = trees + node
        f, thr = feature[at], threshold[at]
        v = flat[rows + f]
        if record:
            path.append((f, v, thr))
        node = 2 * node + 1 + (v > thr)
    return node - n_int, path


def tree_sum(forest: Forest, leaves: np.ndarray, dtype: type) -> np.ndarray:
    t = np.arange(forest.n_trees)
    return forest.leaf[t, leaves].astype(dtype).sum(axis=1, dtype=dtype)


def lear_features(partial: np.ndarray) -> np.ndarray:
    """``[n, 4]``: partial, rank (0 = best, ties by index), min-max
    normalised partial, candidate count."""
    n = partial.size
    rank = np.empty(n, partial.dtype)
    rank[np.lexsort((np.arange(n), -partial))] = np.arange(n)
    lo, hi = partial.min(), partial.max()
    norm = np.clip((partial - lo) / max(hi - lo, 1e-9), 0.0, 1.0)
    count = np.full(n, n, partial.dtype)
    return np.stack([partial, rank, norm, count], axis=1).astype(partial.dtype)


def near_ties(partial: np.ndarray) -> np.ndarray:
    """Per document, how many others have a partial score within
    :data:`TIE_MARGIN`: its float32 rank may be off by that many."""
    order = np.argsort(partial, kind="stable")
    s = partial[order]
    lo = np.searchsorted(s, s - TIE_MARGIN, side="left")
    hi = np.searchsorted(s, s + TIE_MARGIN, side="right")
    ties = np.empty(partial.size, np.int64)
    ties[order] = hi - lo - 1
    return ties


def replay(model: Model, X: np.ndarray, precision: str = "float64") -> Replay:
    """The reference (``float64``) or the control (``bfloat16``) for one
    request's ``[n, F]`` features."""
    if precision == "float64":
        acc, ranker, clf = np.float64, model.ranker, model.classifier
        x = X
    elif precision == "bfloat16":
        acc = np.float32
        ranker = model.ranker.rounded(ml_dtypes.bfloat16)
        clf = model.classifier.rounded(ml_dtypes.bfloat16)
        x = _round(X, ml_dtypes.bfloat16)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    leaves, _ = walk(ranker, x)
    head = ranker.trees(0, model.sentinel)
    partial = tree_sum(head, leaves[:, :model.sentinel], acc)
    full = partial + tree_sum(ranker.trees(model.sentinel, ranker.n_trees),
                              leaves[:, model.sentinel:], acc)
    aug = lear_features(partial)
    c_leaves, path = walk(clf, np.concatenate([x, aug], axis=1).astype(acc),
                          record=True)
    logit = tree_sum(clf, c_leaves, acc)
    cont = logit >= model.logit_threshold
    undecided = np.abs(logit - model.logit_threshold) <= ROUNDING_MARGIN
    undecided |= _near_classifier_threshold(X.shape[1], path,
                                            near_ties(partial))
    return Replay(partial=partial, full=full, cont=cont, undecided=undecided)


def _near_classifier_threshold(n_features: int, path,
                               ties: np.ndarray) -> np.ndarray:
    """Documents whose classifier path tests an augmented feature within
    rounding of its threshold: partial and normalised partial within
    :data:`ROUNDING_MARGIN`, rank within its count of near ties."""
    near = np.zeros(ties.size, bool)
    for f, v, thr in path:
        aug = f - n_features
        gap = np.abs(v - thr)
        hit = ((aug == 0) | (aug == 2)) & (gap <= ROUNDING_MARGIN)
        hit |= (aug == 1) & (ties[:, None] > 0) & (gap <= ties[:, None])
        near |= hit.any(axis=1)
    return near


def expected_scores(ref: Replay, scores: np.ndarray) -> np.ndarray:
    """The reference score each served document should carry: the one its
    decision picks, or for an undecided document whichever is nearer."""
    want = ref.served()
    alt = np.where(ref.cont, ref.partial, ref.full)
    nearer_alt = np.abs(scores - alt) < np.abs(scores - want)
    return np.where(ref.undecided & nearer_alt, alt, want)


def score_error(ref: Replay, scores: np.ndarray) -> float:
    """Largest gap between a served score and the reference's."""
    if scores.shape != ref.full.shape:
        return float("inf")
    return float(np.abs(scores - expected_scores(ref, scores)).max())


def top_k_ok(ref: Replay, scores: np.ndarray, top: np.ndarray, k: int,
             tie: float) -> bool:
    """The served top-k is the reference order of the expected scores,
    except where two documents swap within ``tie`` of each other."""
    if scores.shape != ref.full.shape:
        return False
    want_scores = expected_scores(ref, scores)
    want = np.lexsort((np.arange(want_scores.size), -want_scores))
    k = min(k, want_scores.size)
    top = np.asarray(top)
    if top.shape != (k,):
        return False
    return all(
        a == b or abs(want_scores[a] - want_scores[b]) <= tie
        for a, b in zip(top.tolist(), want[:k].tolist())
    )


def served_top_k(scores: np.ndarray, k: int) -> np.ndarray:
    """Top-k by descending score, ties by ascending index."""
    return np.lexsort((np.arange(scores.size), -scores))[:min(k, scores.size)]
