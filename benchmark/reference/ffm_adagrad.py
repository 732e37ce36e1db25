"""One step of libffm's training rule for a field-aware factorization
machine, numpy, float64: Juan, Zhuang, Chin, Lin, RecSys 2016,
Algorithm 1 (AdaGrad with every accumulator started at 1), which is what
``ffm-train`` runs.

    z_n = w0 + sum_a w[f_a] x_a + sum_{a<b} <v[f_a, field_b], v[f_b, field_a]> x_a x_b
    kappa_n = sw_n * dlogloss(z_n, y_n)/dz_n = sw_n * (sigmoid(z_n) - y_n)
    for every parameter p the rows' pair loops reach:
        g = sum_n kappa_n dz_n/dp + l2 * p;  G <- G + g * g;  p <- p - lr * g / sqrt(G)

A vector ``v[f_a, field_b]`` is reached by a row n that counts
(``sw_n > 0``) and holds feature ``f_a`` in a slot a and ANOTHER slot b
of that field, both with a value other than 0; a linear weight ``w[f_a]``
by any such slot a. Nothing else changes, accumulators included: ``l2``
is paid where a row looked, never over the table. The bias has an
accumulator and no ``l2``.

Departures from libffm, each also the program's:

- a chunk at a time and not an example at a time: the gradients of all
  the chunk's rows are summed (not averaged) at the parameters the chunk
  began with, and every reached parameter moves once. libffm updates
  after each example, so a later example of the same pass already sees
  the earlier one's step;
- a bias and linear weights, which libffm's model lacks and this
  repository's (and ytk-learn's) has; they follow the same rule;
- the reported loss is the weighted mean logloss of the chunk.

It works on the rows a chunk touches, gathered beforehand, as
``reference/ffm.py`` does: ``E[n, a, b] = v[feat[n, a], field[n, b]]``
(table row ``feat[n, a] * n_fields + field[n, b]``), so it never holds a
table. Imports nothing of the system under test.
"""

from __future__ import annotations

import numpy as np


def _apply(index, grads, before, acc, lr, l2):
    """AdaGrad on the distinct entries of ``index`` [M]: sums ``grads``
    [M, ...] by index (``np.add.at``), takes each entry's value and
    accumulator from its first occurrence in ``before`` / ``acc``, and
    returns (distinct index, new values, new accumulators)."""
    uniq, first, inv = np.unique(index, return_index=True,
                                 return_inverse=True)
    g = np.zeros((uniq.size,) + grads.shape[1:])
    np.add.at(g, inv.reshape(-1), grads)
    p = before[first].astype(np.float64)
    g += l2 * p
    G = acc[first].astype(np.float64) + g * g
    return uniq, p - lr * g / np.sqrt(G), G


def step(E, GE, w_slots, Gw_slots, w0, G0, rows, feats, vals, y, sw,
         lr: float, l2: float):
    """Returns ``(loss, (w0, G0), (rows, values, accumulators),
    (features, weights, accumulators))`` after the step: the distinct
    table rows and features the chunk reached, ascending, with their new
    values and new accumulators.

    E, GE: [N, K, K, k] gathered embedding rows and their accumulators;
    w_slots, Gw_slots: [N, K] gathered linear weights and theirs; w0, G0:
    the bias and its accumulator; rows: [N, K, K] table row of each slot
    pair; feats, vals: [N, K]; y, sw: [N]."""
    E = E.astype(np.float64)
    x = vals.astype(np.float64)
    y = y.astype(np.float64)
    sw = sw.astype(np.float64)
    K, k = x.shape[1], E.shape[-1]
    xx = x[:, :, None] * x[:, None, :]
    pair = np.einsum("nabk,nbak->nab", E, E) * xx
    z = (w0 + np.sum(w_slots.astype(np.float64) * x, axis=1)
         + np.sum(pair * np.triu(np.ones((K, K)), 1), axis=(1, 2)))
    logloss = np.maximum(z, 0) - z * y + np.log1p(np.exp(-np.abs(z)))
    loss = np.sum(sw * logloss) / max(np.sum(sw), 1.0)
    kappa = sw * (1.0 / (1.0 + np.exp(-z)) - y)

    live = (x != 0) & (sw[:, None] > 0)
    reached = live[:, :, None] & live[:, None, :] & ~np.eye(K, dtype=bool)
    # d z_n / d E[n, a, b] = E[n, b, a] x_a x_b for a != b
    gE = kappa[:, None, None, None] * np.swapaxes(E, 1, 2) * xx[..., None]
    new_rows = _apply(rows[reached], gE[reached], E.reshape(-1, k)[
        np.flatnonzero(reached.reshape(-1))], GE.reshape(-1, k)[
        np.flatnonzero(reached.reshape(-1))], lr, l2)
    new_w = _apply(feats[live], (kappa[:, None] * x)[live], w_slots[live],
                   Gw_slots[live], lr, l2)

    g0 = float(np.sum(kappa))
    G0 = float(G0) + g0 * g0
    return float(loss), (float(w0) - lr * g0 / np.sqrt(G0), G0), \
        new_rows, new_w
