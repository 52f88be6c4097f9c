"""Work and byte counts against counts worked by hand, and the peaks."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def _shape(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return work.Shape.of(cfg)


# By hand, lear-msn1: a document that exits walks 50 head trees of depth 6
# (7 ops each) and 10 classifier trees of depth 5 (6 ops each):
# 350 + 60 = 410. One that continues adds 997 tail trees: 6,979 more.
# lear-istella: the same head and classifier, 1,419 tail trees: 9,933.
@pytest.mark.parametrize("name, exit_ops, cont_ops", [
    ("lear-msn1", 410, 410 + 6979),
    ("lear-istella", 410, 410 + 9933),
])
def test_ops_by_hand(name, exit_ops, cont_ops):
    s = _shape(name)
    assert work.forest_ops(s, 1, 0) == exit_ops
    assert work.forest_ops(s, 1, 1) == cont_ops
    assert work.forest_ops(s, 100, 29) == 100 * exit_ops + 29 * (
        cont_ops - exit_ops)


# By hand: a depth-6 tree is 63 nodes x (index + threshold) x 4 B + 64
# leaves x 4 B = 760 B; a depth-5 classifier tree 31 x 8 + 32 x 4 = 376 B.
# lear-msn1 tables: 1,047 x 760 + 10 x 376 = 799,480 B; lear-istella:
# 1,469 x 760 + 3,760 = 1,120,200 B. A document that exits reads F
# features for the head, F + 4 for the classifier and writes two scores:
# msn1 (136 + 140 + 2) x 4 = 1,112 B; istella (220 + 224 + 2) x 4 =
# 1,784 B. One that continues reads F more and writes one more score.
@pytest.mark.parametrize("name, tables, exit_b, cont_extra", [
    ("lear-msn1", 799_480, 1_112, 137 * 4),
    ("lear-istella", 1_120_200, 1_784, 221 * 4),
])
def test_bytes_by_hand(name, tables, exit_b, cont_extra):
    s = _shape(name)
    assert work.tree_bytes(s.n_trees, s.depth) + work.tree_bytes(
        s.classifier_trees, s.classifier_depth) == tables
    assert work.forest_bytes(s, 1, 0, 0) == exit_b
    assert work.forest_bytes(s, 1, 1, 0) == exit_b + cont_extra
    assert work.forest_bytes(s, 10, 3, 4) == (
        10 * exit_b + 3 * cont_extra + 4 * tables)


def test_roofline_takes_the_longer_floor():
    pk = work.peak("TPU v5 lite")
    assert pk.flops == 197e12 and pk.hbm_bytes_per_s == 819e9
    assert work.least_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9 * 2, pk) == pytest.approx(2.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peak("cpu")
