"""Work the served window required, and the chip's peaks to hold it against.

Work is counted from the model and the real documents, never from how the
kernel computes it: a kernel that pads rows, gathers features through a
one-hot matmul or walks bit-vectors reads the same work.

- Operations: one compare per level of every tree a document traverses
  plus the leaf add, ``depth + 1`` per document-tree. Every document
  traverses the ``sentinel`` head trees and the classifier's trees; a
  document that continues also traverses the ``n_trees - sentinel`` tail
  trees.
- Bytes: each document's features read once per forest pass it takes (head
  and tail: ``F`` float32 values; classifier: ``F + 4``), its score written
  once per pass, and each launch's tree tables (a complete tree of depth
  ``d``: ``2**d - 1`` nodes of a feature index and a threshold, ``2**d``
  leaf values, 4 bytes each) read once per device per flush.

The compute peak is the chip's bf16 matrix peak. Compares and adds run on
the vector unit, far below it, so no kernel can reach 100% of it; the
shares read far below 1% because the kernel does about 98,000 operations
of one-hot gather per document-tree where the algorithm needs seven.
"""

from __future__ import annotations

import dataclasses

#: Published peaks per ``device_kind``. Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

PASSES_PER_DOC = 2     # head and classifier, for every document
WORD = 4               # bytes of a feature, threshold, index or score


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float
    hbm_bytes_per_s: float


def peak(device_kind: str) -> Peak:
    """The table's peaks for ``device_kind``; an unknown kind is an error."""
    try:
        row = PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add its published "
            f"peaks to bench/work.py") from None
    return Peak(flops=row["flops"], hbm_bytes_per_s=row["hbm_bytes_per_s"])


@dataclasses.dataclass(frozen=True)
class Shape:
    """The served model's shape, from a configuration file."""

    n_trees: int
    depth: int
    n_features: int
    sentinel: int
    classifier_trees: int
    classifier_depth: int

    @classmethod
    def of(cls, config: dict) -> Shape:
        return cls(**{f.name: int(config[f.name])
                      for f in dataclasses.fields(cls)})


def tree_bytes(n_trees: int, depth: int) -> int:
    return n_trees * (((1 << depth) - 1) * 2 * WORD + (1 << depth) * WORD)


def forest_ops(shape: Shape, docs: float, continued: float) -> float:
    """Operations of ``docs`` documents of which ``continued`` continue."""
    per_doc = (shape.sentinel * (shape.depth + 1)
               + shape.classifier_trees * (shape.classifier_depth + 1))
    tail = (shape.n_trees - shape.sentinel) * (shape.depth + 1)
    return docs * per_doc + continued * tail


def forest_bytes(shape: Shape, docs: float, continued: float,
                 launch_sets: float) -> float:
    """Bytes of ``docs`` documents (``continued`` of them continue) served
    in ``launch_sets`` flushes-times-devices, each reading every table."""
    F = shape.n_features
    features = (docs * (F + F + 4) + continued * F) * WORD
    scores = (docs * PASSES_PER_DOC + continued) * WORD
    tables = (tree_bytes(shape.n_trees, shape.depth)
              + tree_bytes(shape.classifier_trees, shape.classifier_depth))
    return features + scores + launch_sets * tables


def least_seconds(ops: float, nbytes: float, pk: Peak) -> float:
    """Roofline: the longer of the compute and the memory floor."""
    return max(ops / pk.flops, nbytes / pk.hbm_bytes_per_s)
