"""Shared numpy-oracle helpers for the check programs (process and
thread families both validate against the same locally-computed expected
values — SURVEY.md section 4)."""

from __future__ import annotations

import numpy as np

NP_REF = {"SUM": np.add, "PROD": np.multiply, "MAX": np.maximum,
          "MIN": np.minimum}


def rank_data(rank: int, length: int, operand, seed_base: int) -> np.ndarray:
    """Deterministic per-rank input (every rank can regenerate every
    other rank's data to compute expectations locally)."""
    rng = np.random.default_rng(seed_base + rank)
    if operand.dtype.kind == "f":
        return rng.standard_normal(length).astype(operand.dtype)
    return rng.integers(1, 4, length).astype(operand.dtype)


def expected_reduce(arrs, op_name: str) -> np.ndarray:
    out = arrs[0].copy()
    for a in arrs[1:]:
        out = NP_REF[op_name](out, a)
    return out


def score_ensemble(trees, bins: np.ndarray, *, depth: int,
                   learning_rate: float, n_bins: int,
                   missing_bin: bool = False, categorical_features=()):
    """Plain scoring of a boosted ensemble (``models/gbdt.py``'s tree
    format) in float64, no jax: what ``GBDTTrainer.predict`` is held to.

    ``trees``: one entry a round, either a tree ``(feature [2^d - 1],
    bin [2^d - 1], direction [2^d - 1], leaf [2^d])`` in level order or,
    for softmax, a tuple of one tree a class. Every row of ``bins``
    ([N, F] integers) goes down every tree by integer compares: a
    numeric split sends ``bin > b`` right (so a node frozen at bin
    ``n_bins - 1`` sends every present value left); under
    ``missing_bin`` bin 0 follows the node's stored direction (1 =
    right), frozen or not; a feature in ``categorical_features`` sends
    ``bin == b`` right, never at ``n_bins - 1``, whatever the direction.

    Returns ``(margins, terms)``, [N] each or [N, n_classes] for
    softmax: the float64 sum of ``learning_rate * leaf`` over the
    rounds, and the sum of its terms' absolute values (what an error
    bound of a lower-precision sum is a share of)."""
    bins = np.asarray(bins)
    trees = list(trees)
    rounds = [rnd if isinstance(rnd[0], (tuple, list)) else (rnd,)
              for rnd in trees]
    n_classes = len(rounds[0]) if rounds else 1
    is_cat = np.zeros(bins.shape[1], bool)
    is_cat[list(categorical_features)] = True
    rows = np.arange(bins.shape[0])
    margins = np.zeros((bins.shape[0], n_classes))
    terms = np.zeros_like(margins)
    for rnd in rounds:
        for c, tree in enumerate(rnd):
            feat, bin_, dir_, leaf = (np.asarray(a) for a in tree)
            node = np.zeros(bins.shape[0], np.int64)
            start = 0
            for d in range(depth):
                idx = start + node
                value, at = bins[rows, feat[idx]], bin_[idx]
                right = value > at
                if missing_bin:
                    right = np.where(value == 0, dir_[idx] > 0, right)
                right = np.where(is_cat[feat[idx]],
                                 (value == at) & (at != n_bins - 1), right)
                node = node * 2 + right
                start += 2 ** d
            term = learning_rate * leaf.astype(np.float64)[node]
            margins[:, c] += term
            terms[:, c] += np.abs(term)
    if rounds and not isinstance(trees[0][0], (tuple, list)):
        return margins[:, 0], terms[:, 0]
    return margins, terms
