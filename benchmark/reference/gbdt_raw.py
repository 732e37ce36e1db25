"""Plain numpy quantile binning in float64 for a float table with empty
cells (NaN): the edges from a seeded row sample, the order statistics an
edge lies between, the bins from whichever edges it is handed (its own,
or the program's, so that edges and bins are judged apart). The trees on
the bins are ``reference/gbdt_missing.py``'s. Imports nothing from the
system under test.

Conventions checked against (not imported from) ``models/binning.py``
with ``missing_bucket=True``: 256 bins, bin 0 reserved for the empty
cells, so 254 edges a column, the ``i / 255`` quantiles, ``i = 1 ..
254``, of the column's values in the sampled rows, numpy's default
linear interpolation between the two order statistics that bracket the
position ``(n - 1) i / 255``; the rows are
``np.random.default_rng(seed).choice(N, sample, replace=False)`` where
the table has more than ``sample``; an edge that comes out NaN (its two
neighbours are infinities of one sign) is +inf; a value x lands in bin
``1 + #{edges <= x}``, an empty cell in bin 0 and nothing else there.

Everything here is ``np.nanquantile`` of float64 values and a plain
compare-count; columns and rows go in blocks on a few threads (sort,
partition and the comparisons release the GIL) only so that a
million-row sample of 968 columns takes seconds and not a minute.
"""

from __future__ import annotations

import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_THREADS = 8
_COLUMNS = 16       # a cache line of every row of an f32 table
_ROWS = 256         # of the compare-count: [256, 968, 254] is 63 MB


def sample_rows(n_rows: int, sample: int | None, seed: int):
    """The rows the edges are fitted on, or None for all of them."""
    if sample is None or n_rows <= sample:
        return None
    return np.random.default_rng(seed).choice(n_rows, sample, replace=False)


def column_edges(values: np.ndarray, n_edges: int):
    """(edges f32 [n_edges], lo f64, hi f64) of one column's values (NaN
    = empty): the ``i / (n_edges + 1)`` quantiles as
    ``np.nanquantile`` gives them for float64 values, rounded to f32
    once, and the two order statistics that bracket each quantile's
    position."""
    qs = np.arange(1, n_edges + 1) / (n_edges + 1)
    v = np.sort(values[~np.isnan(values)].astype(np.float64))
    if not v.size:
        raise ValueError("a column with no value has no quantiles")
    with warnings.catch_warnings():     # inf - inf between sentinels
        warnings.simplefilter("ignore", RuntimeWarning)
        edges = np.nanquantile(v, qs)
    below = np.floor((v.size - 1) * qs).astype(np.int64)
    return (np.where(np.isnan(edges), np.inf, edges).astype(np.float32),
            v[below], v[np.minimum(below + 1, v.size - 1)])


def _in_blocks(fn, n: int, step: int) -> None:
    blocks = [(a, min(a + step, n)) for a in range(0, n, step)]
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(lambda b: fn(*b), blocks))


def edges(X: np.ndarray, n_edges: int, sample: int | None, seed: int):
    """(edges [F, n_edges] f32, lo [F, n_edges] f64, hi likewise): every
    column's ``column_edges`` from the sampled rows of ``X`` [N, F]."""
    rows = sample_rows(X.shape[0], sample, seed)
    if rows is not None:
        rows = np.sort(rows)    # the order is nothing to a quantile
    out = np.empty((X.shape[1], n_edges), np.float32)
    lo = np.empty((X.shape[1], n_edges))
    hi = np.empty((X.shape[1], n_edges))

    def block(start, stop):
        cols = X[:, start:stop] if rows is None else X[rows, start:stop]
        for f, values in enumerate(np.ascontiguousarray(cols.T), start):
            out[f], lo[f], hi[f] = column_edges(values, n_edges)

    _in_blocks(block, X.shape[1], _COLUMNS)
    return out, lo, hi


def bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """int32 [N, F]: ``1 + #{edges[f] <= x}`` for a value, 0 for an
    empty cell: the plain compare-count, whatever order the edges are
    in."""
    out = np.empty(X.shape, np.int32)

    def block(start, stop):
        x = X[start:stop]
        count = (x[:, :, None] >= edges[None]).sum(-1, dtype=np.int32) + 1
        out[start:stop] = np.where(np.isnan(x), 0, count)

    _in_blocks(block, X.shape[0], _ROWS)
    return out


def between_versions(X: np.ndarray, ours: np.ndarray,
                     theirs: np.ndarray) -> np.ndarray:
    """bool [N, F]: the cells whose value lies between the two versions
    of one of its column's edges, ends included, where the versions
    differ: the only cells that two sets of edges, each binned exactly,
    may put in different bins."""
    lo, hi = np.minimum(ours, theirs), np.maximum(ours, theirs)
    moved = ours != theirs
    out = np.zeros(X.shape, bool)
    for f in np.flatnonzero(moved.any(axis=1)):
        x = X[:, f, None]
        out[:, f] = ((x >= lo[f, moved[f]]) & (x <= hi[f, moved[f]])).any(-1)
    return out
