"""One plain SGD step of a field-aware factorization machine, numpy,
float64 (Juan et al., RecSys 2016, eq. 4, with linear terms and a bias):

    z_n = w0 + sum_a w[f_a] x_a + sum_{a<b} <v[f_a, field_b], v[f_b, field_a]> x_a x_b
    loss = mean_n logloss(z_n, y_n);  every parameter p <- p - lr * dloss/dp

It works on the rows a chunk touches, gathered beforehand:
``E[n, a, b] = v[feat[n, a], field[n, b]]`` (table row
``feat[n, a] * n_fields + field[n, b]``), so it never holds a table.
"""

from __future__ import annotations

import numpy as np


def step(E: np.ndarray, w_slots: np.ndarray, w0: float, rows: np.ndarray,
         feats: np.ndarray, vals: np.ndarray, y: np.ndarray, lr: float):
    """Returns (loss, new w0, unique table rows, their new values,
    unique features, their new linear weights).

    E: [N, K, K, k] gathered embedding rows; w_slots: [N, K] gathered
    linear weights; rows: [N, K, K] table row of each slot pair; feats,
    vals: [N, K]; y: [N]."""
    E = E.astype(np.float64)
    x = vals.astype(np.float64)
    y = y.astype(np.float64)
    n, K = x.shape
    pair = np.einsum("nabk,nbak->nab", E, E) * (x[:, :, None] * x[:, None, :])
    upper = np.triu(np.ones((K, K)), 1)
    z = w0 + np.sum(w_slots.astype(np.float64) * x, axis=1) \
        + np.sum(pair * upper, axis=(1, 2))
    loss = np.mean(np.maximum(z, 0) - z * y + np.log1p(np.exp(-np.abs(z))))
    dz = (1.0 / (1.0 + np.exp(-z)) - y) / n            # dloss/dz_n

    # d z_n / d E[n, a, b] = E[n, b, a] x_a x_b for a != b (the pair is
    # counted once, through whichever of (a, b), (b, a) is upper)
    off_diag = 1.0 - np.eye(K)
    gE = (dz[:, None, None, None] * np.swapaxes(E, 1, 2)
          * (x[:, :, None] * x[:, None, :] * off_diag)[..., None])
    uniq, first, inv = np.unique(rows.reshape(-1), return_index=True,
                                 return_inverse=True)
    flat_g = gE.reshape(-1, E.shape[-1])
    grad = np.stack([np.bincount(inv, weights=flat_g[:, c],
                                 minlength=uniq.size)
                     for c in range(E.shape[-1])], axis=1)
    new_rows = E.reshape(-1, E.shape[-1])[first] - lr * grad

    ufeat, ffirst, finv = np.unique(feats.reshape(-1), return_index=True,
                                    return_inverse=True)
    gw = np.bincount(finv, weights=(dz[:, None] * x).reshape(-1),
                     minlength=ufeat.size)
    new_w = w_slots.reshape(-1)[ffirst].astype(np.float64) - lr * gw
    return float(loss), w0 - lr * float(dz.sum()), uniq, new_rows, ufeat, new_w
