"""A wide table of floats in which most cells are empty, as the readers
of ``train_numeric.csv`` hand it over, made from ``--seed`` (the
``gbdt_raw`` adapter's input; ``missing_table.py`` makes the binned table
and is left as it is).

Missingness is blockwise, as in the source, whose columns are
``L<line>_S<station>_F<n>``: a part visits a station and has all of that
station's measurements, or does not and has none. ``layout`` cuts the
width into ``STATIONS`` blocks of contiguous columns, their sizes a
seeded draw between ``STATION_COLUMNS``' ends, and gives each a visit
rate: the stations that hold the label's columns are visited by ``1 -
missing_rate`` of the parts (a label column is present as often as a
cell of ``missing_table``'s is, so the trees have as much to learn as
``gbdt-bosch-968``'s), the others by a seeded rate each, scaled so that
``missing_rate`` of all cells are NaN and none is visited by under
``LEAST_VISIT`` of the parts. Stations are visited independently, and
only a visited station's cells are drawn.

A present value is a per-column affine map (a location and a scale of
its own, fixed by the seed) of a normal draw, rounded to three decimals
as the file's values are: a narrow column has a few hundred distinct
values, ties are common and some quantile edges repeat.

The label is balanced (cut at the median of its score) and is
``missing_table._score``'s construction carried over to values: the
columns of ``missing_table.label_columns`` (0, 242, 484, 725 and 967 of
968), the first four by value, standardised by their own location and
scale (0 where the cell is empty), the last through its missingness: a
row scores ``+MISSING_EFFECT`` where that column is empty or above its
location, ``-MISSING_EFFECT`` where it is present at or below it, so the
best split of that column sends the low values left and the empty cells
RIGHT, with the high ones.

Rows are drawn in chunks of ``CHUNK_ROWS``, chunk k from
``default_rng([seed, k])``, on a few threads: the table depends on the
seed alone, not on the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import missing_table

CHUNK_ROWS = 16_384
STATIONS = 52
STATION_COLUMNS = (2, 80)   # a station's columns: between these, seeded
LEAST_VISIT = 0.01          # no station is visited by fewer of the parts
MOST_VISIT = 0.98
MISSING_EFFECT = missing_table.MISSING_EFFECT
NOISE = missing_table.NOISE


def _widths(rng, stations: int, n_features: int) -> np.ndarray:
    """``stations`` sizes between ``STATION_COLUMNS``' ends that sum to
    ``n_features``: the least everywhere, and the rest dealt out a column
    at a time to stations that are not full, by seeded weights."""
    least, most = STATION_COLUMNS
    if not stations * least <= n_features <= stations * most:
        raise ValueError(f"{n_features} columns do not cut into "
                         f"{stations} stations of {least} to {most}")
    weights = rng.dirichlet(np.full(stations, 2.0))
    widths = np.full(stations, least)
    left = n_features - widths.sum()
    while left:
        room = widths < most
        deal = rng.multinomial(left, weights * room / (weights * room).sum())
        deal = np.minimum(deal, most - widths)
        widths += deal
        left -= deal.sum()
    return widths


def layout(seed: int, n_features: int, missing_rate: float) -> dict:
    """What the seed fixes of the table's columns: ``widths``
    [stations] (they sum to ``n_features``), ``station`` [F] (the block
    a column is in), ``visit`` [stations] (the share of parts that visit
    it), ``loc`` / ``scale`` [F] f32 and ``label``
    (``missing_table.label_columns``). A table narrower than two columns
    a station has fewer stations (the tests' toy sizes)."""
    if not 0.0 < missing_rate < 1.0:
        raise ValueError("missing_rate must be in (0, 1)")
    stations = min(STATIONS, n_features // STATION_COLUMNS[0])
    rng = np.random.default_rng([seed, 0x7ab1e])
    widths = _widths(rng, stations, n_features)
    station = np.repeat(np.arange(stations), widths)
    label = missing_table.label_columns(n_features)
    held = np.zeros(stations, bool)
    held[station[label]] = True
    visit = rng.beta(0.8, 2.0, stations)
    visit[held] = 1.0 - missing_rate
    want = ((1.0 - missing_rate) * n_features
            - (widths * visit)[held].sum())     # columns a part has

    def rest(scale):
        return np.clip(scale * visit[~held], LEAST_VISIT, MOST_VISIT)

    lo, hi = 0.0, 1024.0
    for _ in range(60):         # the scale at which the rest fills `want`
        mid = 0.5 * (lo + hi)
        lo, hi = ((mid, hi) if (widths[~held] * rest(mid)).sum() < want
                  else (lo, mid))
    visit[~held] = rest(0.5 * (lo + hi))
    loc = np.round(rng.normal(0.0, 2.0, n_features), 3)
    scale = np.round(np.exp(rng.normal(-1.0, 1.0, n_features)), 3) + 0.01
    return {"widths": widths, "station": station, "visit": visit,
            "loc": loc.astype(np.float32), "scale": scale.astype(np.float32),
            "label": label}


def _values(z: np.ndarray, lay: dict, cols: slice) -> np.ndarray:
    """Unit normals ``z`` [rows, width] f32 (overwritten) mapped by the
    columns' own location and scale, at three decimals."""
    z *= lay["scale"][cols]
    z += lay["loc"][cols]
    np.multiply(z, np.float32(1000.0), out=z)
    np.rint(z, out=z)
    np.divide(z, np.float32(1000.0), out=z)
    return z


def _score(chunk: np.ndarray, lay: dict, rng) -> np.ndarray:
    """The label's score for the rows of ``chunk`` (f32 [rows, F])."""
    cols = lay["label"]
    v = chunk[:, cols].T
    present = ~np.isnan(v)
    x = np.where(present,
                 (v - lay["loc"][cols, None]) / lay["scale"][cols, None], 0.0)
    by_missingness = np.where(present[4] & (x[4] <= 0.0),
                              -MISSING_EFFECT, MISSING_EFFECT)
    z = 1.5 * x[0] * x[1] + 0.8 * x[2] - 0.5 * x[3] * x[3] + by_missingness
    return (z + NOISE * rng.standard_normal(chunk.shape[0])).astype(
        np.float32)


def raw_table(seed: int, rows: int, n_features: int, missing_rate: float):
    """(X f32 [rows, F] with NaN = empty, y f32 [rows] in {0, 1}, half
    of each)."""
    lay = layout(seed, n_features, missing_rate)
    ends = np.r_[0, np.cumsum(lay["widths"])]
    X = np.empty((rows, n_features), np.float32)
    score = np.empty(rows, np.float32)

    def fill(k: int) -> None:
        lo, hi = k * CHUNK_ROWS, min(rows, (k + 1) * CHUNK_ROWS)
        rng = np.random.default_rng([seed, k])
        out = X[lo:hi]
        out.fill(np.nan)
        visited = (rng.random((hi - lo, len(lay["visit"])), np.float32)
                   < lay["visit"])
        # only the visited stations' cells are drawn: a fifth of them
        for s, (start, stop) in enumerate(zip(ends[:-1], ends[1:])):
            at = np.flatnonzero(visited[:, s])
            out[at, start:stop] = _values(
                rng.standard_normal((at.size, stop - start), np.float32),
                lay, slice(start, stop))
        score[lo:hi] = _score(out, lay, rng)

    with ThreadPoolExecutor(missing_table._threads()) as pool:
        list(pool.map(fill, range(-(-rows // CHUNK_ROWS))))
    y = (score > np.median(score)).astype(np.float32)
    return X, y
