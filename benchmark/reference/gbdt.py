"""Plain numpy GBDT pieces in float64: the root split from bincount
histograms and the gain formula, a router for level-order trees, logloss.

Conventions checked against (not imported from) ``models/gbdt.py``:
logistic loss from zero margins gives g = 0.5 - y, h = 0.25; a split at
bin b sends ``bin <= b`` left; the last bin is never a candidate; gain is
``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``; a tree is (feature [2^d - 1],
bin [2^d - 1], direction, leaf value [2^d]) in level order and a row's
margin gains ``learning_rate * leaf``.
"""

from __future__ import annotations

import numpy as np


def root_gains(bins: np.ndarray, y: np.ndarray, n_bins: int,
               reg_lambda: float) -> np.ndarray:
    """Gain of every (feature, bin) candidate at the root, [F, B] f64;
    the last bin is -inf."""
    g = 0.5 - y.astype(np.float64)
    n_features = bins.shape[1]
    hist_g = np.empty((n_features, n_bins))
    hist_h = np.empty((n_features, n_bins))
    for f in range(n_features):
        col = bins[:, f]
        hist_g[f] = np.bincount(col, weights=g, minlength=n_bins)
        hist_h[f] = 0.25 * np.bincount(col, minlength=n_bins)
    gl, hl = np.cumsum(hist_g, axis=1), np.cumsum(hist_h, axis=1)
    gt, ht = gl[:, -1:], hl[:, -1:]

    def score(gs, hs):
        return gs * gs / (hs + reg_lambda)

    gain = score(gl, hl) + score(gt - gl, ht - hl) - score(gt, ht)
    gain[:, -1] = -np.inf
    return gain


def root_split_ok(gain: np.ndarray, feature: int, bin_: int,
                  rel_tol: float = 1e-6) -> bool:
    """Whether (feature, bin) is the best candidate, or ties with it
    within ``rel_tol`` of the best gain."""
    best = gain.max()
    return bool(gain[feature, bin_] >= best - rel_tol * abs(best))


def route_margins(trees, bins: np.ndarray, depth: int,
                  learning_rate: float) -> np.ndarray:
    """Margins [rows] f64 of an ensemble of level-order trees, each
    ``(feature, bin, direction, leaf)``, by plain routing."""
    margins = np.zeros(bins.shape[0])
    rows = np.arange(bins.shape[0])
    for feat, bin_, _dir, leaf in trees:
        node = np.zeros(bins.shape[0], np.int64)
        start = 0
        for d in range(depth):
            idx = start + node
            right = bins[rows, feat[idx]] > bin_[idx]
            node = node * 2 + right
            start += 2 ** d
        margins += learning_rate * leaf[node].astype(np.float64)
    return margins


def logloss(margins: np.ndarray, y: np.ndarray) -> float:
    z = margins.astype(np.float64)
    return float(np.mean(np.maximum(z, 0) - z * y + np.log1p(np.exp(-np.abs(z)))))
