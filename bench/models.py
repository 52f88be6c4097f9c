"""Seeded model weights and the classifier threshold of a configuration.

A configuration file (``bench/configs/<name>.json``) gives the published
shapes. The trees are drawn here as plain heap-ordered arrays, in the same
order of draws as ``repro.forest.ensemble.random_ensemble``; the harness
hands those arrays to the program, and the reference walks them itself.

The program compiles its weights and its threshold into the serving step as
constants, so a model that changed with every ``--seed`` would make every
run a cold compile. A deployment restarts with its model, so the weights
come from the configuration's own ``weights_seed`` and the traffic from
``--seed``.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference, traffic


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of ``seed`` (any integer)."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def draw_forest(gen: np.random.Generator, n_trees: int, depth: int,
                n_features: int, leaf_scale: float) -> reference.Forest:
    n_int = (1 << depth) - 1
    feature = gen.integers(0, n_features, size=(n_trees, n_int))
    threshold = gen.normal(size=(n_trees, n_int)).astype(np.float32)
    leaf = (leaf_scale * gen.normal(size=(n_trees, 1 << depth))).astype(
        np.float32)
    return reference.Forest(feature, threshold, leaf)


def logit_threshold(ranker: reference.Forest, classifier: reference.Forest,
                    sentinel: int, continue_share: float,
                    sample: list[np.ndarray]) -> float:
    """The logit above which ``continue_share`` of the sample's documents
    continue, rounded to 1e-6 so that it is the same number on any host."""
    head = ranker.trees(0, sentinel)
    logits = []
    for X in sample:
        leaves, _ = reference.walk(head, X)
        partial = reference.tree_sum(head, leaves, np.float64)
        aug = reference.lear_features(partial)
        c_leaves, _ = reference.walk(
            classifier, np.concatenate([X, aug], axis=1))
        logits.append(reference.tree_sum(classifier, c_leaves, np.float64))
    q = float(np.quantile(np.concatenate(logits), 1.0 - continue_share))
    return round(q, 6)


def build(config: dict) -> reference.Model:
    """The configuration's ranker, classifier and threshold, from its
    ``weights_seed``."""
    seed = config["weights_seed"]
    ranker = draw_forest(rng(seed, 0), config["n_trees"], config["depth"],
                         config["n_features"], config["leaf_scale"])
    classifier = draw_forest(rng(seed, 1), config["classifier_trees"],
                             config["classifier_depth"],
                             config["n_features"] + reference.N_AUG,
                             config["leaf_scale"])
    cal = config["threshold_sample"]
    gen = rng(seed, 2)
    sizes = traffic.doc_counts(cal["docs"], cal["queries"], gen)
    sample = [gen.standard_normal((int(n), config["n_features"]),
                                  dtype=np.float32) for n in sizes]
    thr = logit_threshold(ranker, classifier, config["sentinel"],
                          config["continue_share"], sample)
    return reference.Model(ranker, classifier, config["sentinel"], thr)


def probability(logit: float) -> float:
    """The service's threshold on P(continue) for a logit threshold."""
    return 1.0 / (1.0 + math.exp(-logit))
