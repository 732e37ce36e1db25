"""Plain numpy scoring of a boosted ensemble on a FLOAT table with empty
cells (NaN): every cell is binned against its column's edges, every row
goes down every tree by integer compares on those bins, and a row's
margin is the float64 sum of ``learning_rate * leaf`` over the trees.
Imports nothing from the system under test; the walk and the sum are
``reference/gbdt_score.py``'s, the binning rule is ``reference/
gbdt_raw.py``'s, written here a second way (a binary search a column in
float64, where that file counts compares) so that the two can be held
against each other.

Conventions checked against (not imported from) ``models/binning.py``
with ``missing_bucket=True`` and ``models/gbdt.py``: a column's edges
are f32 (repeated edges are common in three-decimal data; an edge may
be +-inf, and an edge is never NaN); a value x, +-inf included, lands in bin ``1
+ #{edges <= x}``, so a value EQUAL to an edge lies above it; an empty
cell lands in bin 0 and nothing else does; f32 values and f32 edges
compare in float64 as they do in f32 (the conversion is exact). Then
``gbdt_score``'s: ``bin <= b`` goes left, bin 0 follows the node's
stored direction, a node frozen at ``n_bins - 1`` sends every present
value left.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import gbdt_score

# The margin's limit is the accepted scoring cell's, carried over: the
# routing is exact or a whole leaf is off, so what is left to a margin
# is its f32 sum of 500 terms (``gbdt_score.MARGIN_REL_ERR`` says what
# the two readings were that the limit lies between).
MARGIN_REL_ERR = gbdt_score.MARGIN_REL_ERR


def bins(X: np.ndarray, edges: np.ndarray,
         missing_bucket: bool = True) -> np.ndarray:
    """int32 [N, F]: ``1 + #{edges[f] <= x}`` for a value, 0 for an
    empty cell, by ``searchsorted(side="right")`` on each column's
    edges in float64. Without ``missing_bucket`` (a binner that
    reserves no bin) a value lands in ``#{edges[f] <= x}`` and an empty
    cell shares bin 0 with the lowest values."""
    X = np.asarray(X)
    edges = np.asarray(edges, np.float64)
    if X.ndim != 2 or edges.shape[0] != X.shape[1]:
        raise ValueError(f"X {X.shape} and edges {edges.shape} disagree")
    # a count takes no notice of the order: a column's edges are sorted
    # for the search (an edge between -inf and a value comes out +inf
    # and stands where its quantile did)
    edges = np.sort(edges, axis=1)
    out = np.empty(X.shape, np.int32)
    first = 1 if missing_bucket else 0
    for f in range(X.shape[1]):
        x = X[:, f].astype(np.float64)
        # NaN sorts after every edge: the count is taken for it too and
        # then overruled
        out[:, f] = np.where(
            np.isnan(x), 0,
            first + np.searchsorted(edges[f], x, side="right"))
    return out


def score(trees, X: np.ndarray, edges: np.ndarray, depth: int,
          learning_rate: float, n_bins: int, missing_bin: bool = True,
          missing_bucket: bool = True):
    """(margins [rows] f64, the sum of |learning_rate * leaf| over each
    row's terms [rows] f64, the bins int32 [rows, F] the rows were
    walked on); the walk takes its rows in blocks, so a large sample
    fits."""
    binned = bins(X, edges, missing_bucket)
    margins, terms = gbdt_score.score_ensemble(
        trees, binned, depth, learning_rate, n_bins, missing_bin)
    return margins, terms, binned


margin_error = gbdt_score.margin_error
