"""Kernels: percent of the document-tree slots the forest launches scored
that the window's ranking required. Required: every document through the
``sentinel`` head trees and the classifier's trees, and each continuing
document through the ``n_trees - sentinel`` tail trees. Scored
(``ServiceStats.doc_tree_slots``, counted by the program from the launch
shapes): rows times tree slots of the head, classifier and tail launches,
padded rows and padded tree blocks included. A program without the
counter reads nothing."""


def read(record):
    s = record.service
    if s.get("doc_tree_slots", 0) <= 0:
        return None
    shape = record.shape
    required = (s["docs"] * (shape.sentinel + shape.classifier_trees)
                + s["docs_continued"] * (shape.n_trees - shape.sentinel))
    return 100.0 * required / s["doc_tree_slots"]
