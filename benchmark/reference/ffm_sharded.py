"""One plain SGD step of a field-aware factorization machine, numpy,
float64 (Juan et al., RecSys 2016, eq. 4, with linear terms and a bias),
for the cell whose table is sharded over four chips:

    z_n = w0 + sum_a w[f_a] x_a + sum_{a<b} <v[f_a, field_b], v[f_b, field_a]> x_a x_b
    loss = mean_n logloss(z_n, y_n);  every parameter p <- p - lr * dloss/dp

It works on the rows a chunk touches, gathered beforehand:
``E[n, a, b] = v[feat[n, a], field[n, b]]`` (table row
``feat[n, a] * n_fields + field[n, b]``), so it never holds a table. It
knows nothing of owners, shards, blocks or rounds of an exchange, and
that is the point: whoever holds a row, the row's update is the sum of
the gradients of every row of the chunk that touches it. Imports nothing
from the system under test. ``reference/ffm.py`` is the same arithmetic;
this copy also hands out what the cell's limits are stated in: the
UPDATE of every touched row and weight, and the sum of the absolute
values of the terms of a weight's update.

What the cell holds the first chunk's step to (``adapters/ffm_sharded.py``
applies them; the readings are in PERF.md, Findings, PR 38):

- the loss, the bias and every touched value within ``RTOL`` and ``ATOL``
  of the float64 step: the configuration's stated guarantee;
- a touched table entry's update within ``ATOL_UPDATE`` (and ``RTOL`` of
  itself) of the reference's: the stored f32 value rounds (half an ulp
  of a value below 2^-4 is 1.9e-9, once for each member whose list
  holds the feature), while the update of a feature that ONE row holds
  is about 3e-8 an entry and up to 9e-8: an owner that drops a block, or
  a round that is not run, fails here whichever feature it loses;
- a linear weight's update (the weights start at 0, so the value is the
  update and nothing rounds but the arithmetic) within ``W_TERMS_RTOL``
  of the sum of the absolute values of its terms. On the chip the f32
  step reads 1.0e-6 of them (the chip's f32 sigmoid, which moves every
  row's dz by about that much of itself; the CPU's reads 2.5e-7); with
  the blocks rounded to bf16 on their way to the requester it reads
  2.4e-5 (every row's dz moves by some 1e-5 of itself), with the
  gradients rounded too 3.9e-3: the limit lies five times above the
  first reading and five times below the second.
"""

from __future__ import annotations

import numpy as np

RTOL = 1e-4             # f32 step against the f64 reference
ATOL = 1e-7             # on a value: the accepted FFM cell's
ATOL_UPDATE = 2e-8      # on a table entry's update
W_TERMS_RTOL = 5e-6     # a weight's update, of its terms' absolute sum


def step(E: np.ndarray, rows: np.ndarray, feats: np.ndarray,
         vals: np.ndarray, y: np.ndarray, lr: float):
    """One step from linear weights and a bias of 0. Returns a dict:
    ``loss``, ``w0`` (the new bias), ``rows`` (the unique table rows),
    ``before`` and ``update`` (their values in ``E`` and what the step
    adds to them, [R, k]), ``feats`` (the unique features), ``w`` (their
    new linear weights, which are their updates) and ``w_terms`` (the sum
    of the absolute values of each one's terms).

    E: [N, K, K, k] gathered embedding rows; rows: [N, K, K] table row of
    each slot pair; feats, vals: [N, K]; y: [N]."""
    E = E.astype(np.float64)
    x = vals.astype(np.float64)
    y = y.astype(np.float64)
    n, K = x.shape
    xx = x[:, :, None] * x[:, None, :]
    pair = np.einsum("nabk,nbak->nab", E, E) * xx
    z = np.sum(pair * np.triu(np.ones((K, K)), 1), axis=(1, 2))
    loss = np.mean(np.maximum(z, 0) - z * y + np.log1p(np.exp(-np.abs(z))))
    dz = (1.0 / (1.0 + np.exp(-z)) - y) / n            # dloss/dz_n

    # d z_n / d E[n, a, b] = E[n, b, a] x_a x_b for a != b (the pair is
    # counted once, through whichever of (a, b), (b, a) is upper)
    gE = (dz[:, None, None, None] * np.swapaxes(E, 1, 2)
          * (xx * (1.0 - np.eye(K)))[..., None])
    uniq, first, inv = np.unique(rows.reshape(-1), return_index=True,
                                 return_inverse=True)
    k = E.shape[-1]
    flat_g = gE.reshape(-1, k)
    grad = np.stack([np.bincount(inv, weights=flat_g[:, c],
                                 minlength=uniq.size)
                     for c in range(k)], axis=1)

    ufeat, finv = np.unique(feats.reshape(-1), return_inverse=True)
    terms = (dz[:, None] * x).reshape(-1)
    gw = np.bincount(finv, weights=terms, minlength=ufeat.size)
    w_terms = np.bincount(finv, weights=np.abs(terms), minlength=ufeat.size)
    return {"loss": float(loss), "w0": -lr * float(dz.sum()),
            "rows": uniq, "before": E.reshape(-1, k)[first],
            "update": -lr * grad,
            "feats": ufeat, "w": -lr * gw, "w_terms": lr * w_terms}
