"""Plain numpy scoring of a boosted ensemble in float64: every row goes
down every tree by integer compares, and a row's margin is the float64
sum of ``learning_rate * leaf`` over the trees. Imports nothing from the
system under test.

Conventions checked against (not imported from) ``models/gbdt.py``: a
tree is (feature [2^d - 1], bin [2^d - 1], direction [2^d - 1], leaf
value [2^d]) in level order; a numeric split at bin b sends ``bin <= b``
left and ``bin > b`` right, so a node frozen at bin B - 1 sends every
present value left; under ``missing_bin`` a missing cell (bin 0) follows
the stored direction of its node (0 left, 1 right), frozen or not; a
categorical feature splits by equality (``bin == b`` right), never at
B - 1, and takes no notice of the direction.
"""

from __future__ import annotations

import numpy as np

# What the configuration states of a margin: within 2^-18 (3.81e-6) of
# the sum of |learning_rate * leaf| over its terms. The two readings the
# limit lies between (PERF.md, Findings, PR 30): the system's own f32 sum
# of 500 f32 terms in tree order reads 1.4e-7 to 2.3e-7 of the terms (21
# runs of the cell on the chip, 21 seeds; this reference summed the same
# way in f32 reads 1.7e-7 to 2.1e-7), a sixteenth of the limit; this
# reference with every leaf rounded to bf16 (2^-9 of itself) reads
# 3.7e-4 to 4.2e-4, a hundred times the limit.
MARGIN_REL_ERR = 2.0 ** -18
_BLOCK_ROWS = 8_192


def leaf_of(tree, bins: np.ndarray, depth: int, n_bins: int,
            missing_bin: bool, categorical=()) -> np.ndarray:
    """The leaf [rows] each row of ``bins`` reaches in one tree."""
    feat, bin_, dir_, _ = tree
    rows = np.arange(bins.shape[0])
    is_cat = np.zeros(bins.shape[1], bool)
    is_cat[list(categorical)] = True
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
    return node


def score_ensemble(trees, bins: np.ndarray, depth: int, learning_rate: float,
                   n_bins: int, missing_bin: bool, categorical=()):
    """(margins [rows] f64, the sum of |learning_rate * leaf| over each
    row's terms [rows] f64), rows taken in blocks."""
    margins = np.zeros(bins.shape[0])
    terms = np.zeros(bins.shape[0])
    for lo in range(0, bins.shape[0], _BLOCK_ROWS):
        block = bins[lo:lo + _BLOCK_ROWS]
        for tree in trees:
            term = learning_rate * np.asarray(tree[3], np.float64)[
                leaf_of(tree, block, depth, n_bins, missing_bin, categorical)]
            margins[lo:lo + _BLOCK_ROWS] += term
            terms[lo:lo + _BLOCK_ROWS] += np.abs(term)
    return margins, terms


def margin_error(got: np.ndarray, want: np.ndarray,
                 terms: np.ndarray) -> float:
    """The largest error of ``got`` as a share of its row's terms (the
    share that ``MARGIN_REL_ERR`` bounds); a row with no terms must be
    exactly zero."""
    off = np.abs(np.asarray(got, np.float64) - want)
    share = np.divide(off, terms, out=np.where(off > 0, np.inf, 0.0),
                      where=terms > 0)
    return float(share.max()) if share.size else 0.0
