"""Plain numpy scoring with a field-aware factorization machine in
float64 (Juan et al., RecSys 2016, eq. 4, with linear terms and a bias,
what libffm's ``ffm-predict`` computes before the sigmoid):

    z_n = w0 + sum_a w[j_a] x_a
             + sum_{a<b} <v[j_a, f_b], v[j_b, f_a]> x_a x_b

over the slots of a row that hold a feature (a padded slot has x = 0 and
adds nothing), from the public ``(w0, w, V)``: ``v[j, f]`` is row
``j * n_fields + f`` of ``V``. Imports nothing from the system under
test. Rows are taken a block at a time, so that what it holds is a
block's ``[rows, K, K, k]`` and not the file's.
"""

from __future__ import annotations

import numpy as np

# What the configuration states of a margin (the logit of the returned
# probability): within 2^-16 (1.53e-5) of the sum of the absolute values
# of its terms (|w0|, every |w x| and every |<v, v> x x|) of the float64
# score. The two readings the limit lies between (PERF.md, Findings, PR
# 36): the system's own output reads 1.16e-6 to 1.24e-6 of the terms (my
# chip runs, 8 seeds, about 73,600 rows each), a twelfth of the limit,
# nearly all of it the chip's f32 sigmoid (5e-6 absolute beside terms of
# 4.35: the score itself, fetched before the link, reads 4.6e-8); this
# reference with every table entry and linear weight rounded to bf16
# (2^-9 of itself) reads 4.4e-4 to 4.8e-4, thirty times the limit, and
# so does the system with its gathered blocks rounded to bf16 or its
# select at the default precision (4.82e-4 each on the chip).
MARGIN_REL_ERR = 2.0 ** -16
_BLOCK_ROWS = 4_096


def score(w0, w: np.ndarray, V: np.ndarray, feats: np.ndarray,
          fields: np.ndarray, vals: np.ndarray, n_fields: int):
    """(margins [rows] f64, the sum of the absolute values of each
    row's terms [rows] f64). ``w`` [n_features], ``V`` [n_features *
    n_fields, k]; feats, fields, vals: [rows, K]."""
    w = np.asarray(w, np.float64)
    K = feats.shape[1]
    upper = np.triu(np.ones((K, K)), 1)
    margins = np.zeros(feats.shape[0])
    terms = np.zeros(feats.shape[0])
    for lo in range(0, feats.shape[0], _BLOCK_ROWS):
        f, fl = feats[lo:lo + _BLOCK_ROWS], fields[lo:lo + _BLOCK_ROWS]
        x = np.asarray(vals[lo:lo + _BLOCK_ROWS], np.float64)
        # E[n, a, b] = v[j_a, f_b]
        E = np.asarray(V[f[:, :, None] * n_fields + fl[:, None, :]],
                       np.float64)
        pair = (np.einsum("nabk,nbak->nab", E, E)
                * (x[:, :, None] * x[:, None, :]) * upper)
        linear = w[f] * x
        margins[lo:lo + _BLOCK_ROWS] = (float(w0) + linear.sum(axis=1)
                                        + pair.sum(axis=(1, 2)))
        terms[lo:lo + _BLOCK_ROWS] = (abs(float(w0))
                                      + np.abs(linear).sum(axis=1)
                                      + np.abs(pair).sum(axis=(1, 2)))
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


def logit(p: np.ndarray) -> np.ndarray:
    """The margin a probability came from, in float64."""
    p = np.asarray(p, np.float64)
    return np.log(p) - np.log1p(-p)
