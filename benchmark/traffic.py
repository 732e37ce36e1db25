"""The one traffic generator: every input of every cell, made from
``--seed`` and the parameters in the cell's configuration and traffic
files. A cell that needs other numbers adds a data file, not code here.

- ``binned_table``: a table of binned features with a learnable binary
  label (the GBDT cells). It is ``bench.make_data``'s uniform bins with
  ``chip_smoke.higgs_like``'s nonlinear noisy target put on them, so that
  11M rows cost two seconds and trees have something to learn.
- ``zipf_chunk_pool``: a pool of padded-sparse minibatches, one feature a
  field, ids Zipf-skewed inside each field's own range (the FFM cells).
- ``small_ints``: exact small-integer operands for collectives, the same
  formula for numpy (the reference) and jax.numpy (the device).
"""

from __future__ import annotations

import numpy as np


def binned_table(seed: int, rows: int, n_features: int, n_bins: int):
    """(bins int32 [rows, F] uniform in [0, n_bins), y f32 [rows] in
    {0, 1}). The label is a nonlinear function of the first four
    features plus noise; both classes are about even."""
    if n_features < 4 or not 2 <= n_bins <= 256:
        raise ValueError("binned_table needs >= 4 features and 2..256 bins")
    rng = np.random.default_rng(seed)
    b8 = rng.integers(0, n_bins, (rows, n_features), dtype=np.uint8)
    # four contiguous columns in [-1, 1]: strided ones cost twice the time
    x = np.ascontiguousarray(b8[:, :4].T).astype(np.float32)
    x = x * np.float32(2.0 / (n_bins - 1)) - np.float32(1.0)
    z = (np.float32(1.5) * x[0] * x[1] + np.float32(0.8) * x[2]
         - np.float32(0.5) * x[3] * x[3] + np.float32(1.0 / 6.0))
    noise = rng.standard_normal(rows, dtype=np.float32)
    y = (z + np.float32(0.3) * noise > 0).astype(np.float32)
    return b8.astype(np.int32), y


def zipf_chunk_pool(seed: int, n_features: int, n_fields: int, rows: int,
                    chunks: int, exponent: float, positive_rate: float):
    """``chunks`` minibatches of ``rows`` instances with ``n_fields``
    slots each: slot f holds one feature of field f, drawn from that
    field's own ``n_features // n_fields`` ids by a Zipf law of the given
    exponent (0 is uniform) through a fixed permutation, so hot ids are
    scattered over the table as hashing scatters them. Values are 1.0,
    labels Bernoulli(``positive_rate``). Returns a list of
    ``(feats, fields, vals, y)`` as ``FMTrainer.fit_stream`` takes them."""
    per_field = n_features // n_fields
    if per_field < 1:
        raise ValueError("fewer features than fields")
    rng = np.random.default_rng(seed)
    weights = np.arange(1, per_field + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    perm = rng.permutation(per_field).astype(np.int32)
    base = np.arange(n_fields, dtype=np.int32)[None, :] * per_field
    fields = np.ascontiguousarray(np.broadcast_to(
        np.arange(n_fields, dtype=np.int32), (rows, n_fields)))
    vals = np.ones((rows, n_fields), np.float32)
    pool = []
    for _ in range(chunks):
        rank = np.searchsorted(cdf, rng.random((rows, n_fields)))
        rank = np.minimum(rank, per_field - 1)
        y = (rng.random(rows) < positive_rate).astype(np.float32)
        pool.append((base + perm[rank], fields, vals, y))
    return pool


def small_ints(xp, index, rank, seed):
    """Element ``index`` (uint32 array of ``xp`` = numpy or jax.numpy) of
    rank ``rank``'s operand: an integer in [0, 8) as f32, so that sums
    over a few ranks, and their halves and quarters, are exact. ``rank``
    and ``seed`` may be traced scalars; everything wraps modulo 2**32."""
    def u32(v):
        return xp.asarray(v).astype(xp.uint32).reshape(1)
    mixed = (index * xp.uint32(2654435761) + u32(rank) * xp.uint32(40503)
             + u32(seed) * xp.uint32(9176) + xp.uint32(12345))
    return ((mixed >> xp.uint32(13)) & xp.uint32(7)).astype(xp.float32)
