"""A wide binned table in which most cells are missing, made from
``--seed`` (the ``gbdt_missing`` adapter's input; ``traffic.py`` makes the
dense tables and is left as it is).

Bin 0 is the reserved missing bucket (``GBDTConfig.missing_bin``): each
cell is missing independently with probability ``missing_rate`` and a
present cell is uniform in bins 1 .. n_bins - 1. The label is balanced
(cut at the median of its score) and leans on five columns spread
evenly from the first to the last (``label_columns``: 0, 242, 484, 725
and 967 of 968, so the histogram kernel's first, middle and last
feature blocks each decide splits): on the present values of the first
four the way ``traffic.binned_table``'s does, and on the last through
its missingness: a row scores ``+MISSING_EFFECT`` where that column is
missing or its bin is above the middle, ``-MISSING_EFFECT`` where it is
present at or below the middle. The best split of that column therefore
sends "bin <= middle" left and the missing rows RIGHT, with the high
bins: a partition that no split with missing on the left can make, so a
trainer that learns default directions stores direction 1 there.

Rows are drawn in chunks of ``CHUNK_ROWS``, chunk k from
``default_rng([seed, k])``, on a few threads (numpy's generators and
ufuncs release the GIL): the table depends on the seed alone, not on the
number of threads.

``label_draw`` gives the same table another label: the same score of the
same bins with its noise drawn anew (chunk k of draw d from
``default_rng([seed, k, d])``), cut at its own median. Half of the rows
score within the noise of the cut, so a draw moves about three labels in
eight; a traffic mix that hands successive jobs successive draws
(``traffic/train-relabelled.json``) averages, inside one run, what a
tree's near-ties happen to do under one draw.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 16_384
LABEL_COLUMNS = 5           # four by value, the last by missingness
MISSING_EFFECT = 1.0
NOISE = 0.3


def _threads() -> int:
    return max(1, min(12, (os.cpu_count() or 1) - 1))


def label_columns(n_features: int) -> np.ndarray:
    """The columns the label leans on: ``LABEL_COLUMNS`` of them, evenly
    from column 0 to column ``n_features - 1``."""
    return np.round(np.linspace(0, n_features - 1,
                                LABEL_COLUMNS)).astype(np.int64)


def _score(chunk: np.ndarray, n_bins: int, rng) -> np.ndarray:
    """The label's score for the rows of ``chunk`` (int32 [rows, F])."""
    b = chunk[:, label_columns(chunk.shape[1])].T.astype(np.float32)
    present = b > 0
    # present bins 1 .. n_bins-1 mapped onto [-1, 1]; a missing value is 0
    x = np.where(present, (b - 1) * (2.0 / (n_bins - 2)) - 1.0, 0.0)
    middle = n_bins // 2
    by_missingness = np.where(present[4] & (b[4] <= middle),
                              -MISSING_EFFECT, MISSING_EFFECT)
    z = 1.5 * x[0] * x[1] + 0.8 * x[2] - 0.5 * x[3] * x[3] + by_missingness
    return (z + NOISE * rng.standard_normal(chunk.shape[0])).astype(
        np.float32)


def missing_binned_table(seed: int, rows: int, n_features: int, n_bins: int,
                         missing_rate: float):
    """(bins int32 [rows, F] with bin 0 = missing, y f32 [rows] in {0, 1},
    half of each)."""
    if n_features < LABEL_COLUMNS or not 4 <= n_bins <= 256:
        raise ValueError("missing_binned_table needs >= 5 features and "
                         "4..256 bins")
    if not 0.0 <= missing_rate < 1.0:
        raise ValueError("missing_rate must be in [0, 1)")
    threshold = int(round(missing_rate * 65536))
    bins = np.empty((rows, n_features), np.int32)
    score = np.empty(rows, np.float32)

    def fill(k: int) -> None:
        lo, hi = k * CHUNK_ROWS, min(rows, (k + 1) * CHUNK_ROWS)
        rng = np.random.default_rng([seed, k])
        shape = (hi - lo, n_features)
        value = rng.integers(1, n_bins, shape, dtype=np.uint8)
        present = rng.integers(0, 65536, shape, dtype=np.uint16) >= threshold
        np.multiply(value, present, out=bins[lo:hi], casting="unsafe")
        score[lo:hi] = _score(bins[lo:hi], n_bins, rng)

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, range(-(-rows // CHUNK_ROWS))))
    y = (score > np.median(score)).astype(np.float32)
    return bins, y


def label_draw(bins: np.ndarray, n_bins: int, seed: int, draw: int):
    """y f32 [rows] in {0, 1}, half of each: the label of
    ``missing_binned_table``'s ``bins`` with the score's noise drawn anew
    (``draw`` >= 1; the table's own label is draw 0 and is not remade
    here: its noise comes after the cells' in the chunk's stream)."""
    if draw < 1:
        raise ValueError("draw 0 is the label missing_binned_table returns")
    rows = bins.shape[0]
    score = np.empty(rows, np.float32)

    def fill(k: int) -> None:
        lo, hi = k * CHUNK_ROWS, min(rows, (k + 1) * CHUNK_ROWS)
        score[lo:hi] = _score(bins[lo:hi], n_bins,
                              np.random.default_rng([seed, k, draw]))

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, range(-(-rows // CHUNK_ROWS))))
    return (score > np.median(score)).astype(np.float32)
