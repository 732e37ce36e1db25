"""Quantile feature binning — continuous features -> GBDT bin ids.

The reference's GBDT consumer (ytk-learn) bins continuous features into
<=256 quantile buckets before histogram building; this is that front
end rebuilt TPU-first. Bin edges are the order statistics of (a row
sample of) the data; the transform is the comparison count ``bin(x) =
#edges <= x``, found by upper-bound search in a column's sorted edges:
8 probes a cell at 254 edges (``_count_edges``: the one compare-count
there is). On a TPU it is the Mosaic kernel ``mp4j_bin``
(``ops/bin_kernel.py``), whose probe is a gather inside a vector
register and not the serial gather unit XLA's searchsorted would use;
elsewhere the same walk in ``jnp``.

What runs where. ``fit`` takes a host array and fits on the host
(``np.nanquantile``, a column at a time; the weighted and the
distributed sketches too). ``fit_staged`` fits the same edges, to the
bit, on a float table that already rests on a mesh: the sample's rows
are picked there, its columns sorted there a block at a time, and the
order statistics every edge lies between read there (``_sketch``); the
host interpolates the 254 picked pairs a column as numpy does.
``transform_staged`` bins such a table where it rests, into the int32
table a ``GBDTTrainer`` step takes; ``transform`` takes a host array,
sends it through the same program a chunk of rows at a time and fetches
the bins (public API for a caller who wants bins on the host; no product
path calls it). ``GBDTTrainer.train_raw_chunks`` / ``train_raw`` stage
the floats once and use the staged pair, and ``predict_raw_chunks`` /
``predict_raw`` bin a chunk's rows inside the scoring program
(``_count_edges`` on the rows ``gbdt.score_shard`` slices), so a binned
table crosses the host link in neither direction.

Distributed fitting (``fit_distributed``): each rank sketches its own
shard — per-feature quantile edges plus finite-value counts — and the
fixed-size sketches ride ONE ``allgather_array`` on any SPMD backend
(``ProcessCommSlave`` / ``ThreadCommSlave`` / ``DistributedComm``);
every rank then merges the pooled sketches identically, so all ranks
end with the same edges without ever centralizing raw features. The
merge treats each rank's sketch ``[min, q_1/Q, ..., q_(Q-1)/Q, max]``
as a piecewise-linear CDF through per-point (value, cdf) pairs,
count-weight-averages the per-rank CDFs (left and right limits, so
tied-value jumps survive pooling), and inverts the pooled CDF at the
target quantiles — exact when one rank holds a feature's
distinct-valued data, O(1/Q) in quantile space across ranks, and
TIE-ROBUST: repeated values carry their true empirical mass through
the merge via the sketch's cdf row (round 4; tested against the
single-host fit, including 90%-mass-in-5-values, in
``tests/test_binning.py``).
"""

from __future__ import annotations

import warnings
from functools import lru_cache
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.ops import bin_kernel


class FeatureSketch(NamedTuple):
    """One rank's distributed-fit contribution (see ``local_sketch``).

    values: [F, Q+1] quantile points ``[min, q_{1/Q}, ..., max]``.
    counts: [F] merge weights (full-shard non-NaN counts).
    finite: [F] 1.0 where the sketched rows hold any finite value.
    cdf:    [F, Q+1] the CDF ordinate of each value point. Equals the
            grid ``[0, 1/Q, ..., 1]`` for distinct-valued data; runs of
            TIED value points carry the shard's TRUE empirical CDF jump
            (left limit at the run start, right limit at the run end) so
            repeated values keep their mass through the merge — the
            weighted-quantile-sketch fix.
    """

    values: np.ndarray
    counts: np.ndarray
    finite: np.ndarray
    cdf: np.ndarray


def _check_weights(sample_weight, n_rows: int) -> np.ndarray:
    """Validate instance weights for the weighted sketch paths:
    [N] finite non-negative, not identically zero."""
    sw = np.asarray(sample_weight, np.float64)
    if sw.shape != (n_rows,):
        raise Mp4jError(
            f"sample_weight must be [N={n_rows}], got {sw.shape}")
    if not np.isfinite(sw).all() or (sw < 0).any():
        raise Mp4jError(
            "sample_weight must be finite and non-negative")
    if n_rows and not (sw > 0).any():
        raise Mp4jError("sample_weight sums to zero: no weighted mass "
                        "to fit quantiles from")
    return sw


def _sorted_weighted_col(col, w):
    """One feature column -> (sorted values, cumulative weights) with
    NaN and zero-weight rows dropped. Returns (None, None) when no
    weighted data remains."""
    m = ~np.isnan(col) & (w > 0)
    v, wv = col[m], w[m]
    if v.size == 0:
        return None, None
    o = np.argsort(v, kind="stable")
    return v[o], np.cumsum(wv[o])


def _wq_inverted_cdf(v_sorted, cw, qs):
    """Weighted quantiles, inverted-CDF convention: the smallest value
    whose weighted CDF reaches q — ``np.quantile(...,
    method="inverted_cdf", weights=...)`` and the classic GBDT weighted
    quantile sketch both define quantiles this way, and it is exact
    under ties (integer weights == row duplication, property-tested)."""
    pos = np.searchsorted(cw, np.asarray(qs) * cw[-1], side="left")
    return v_sorted[np.minimum(pos, v_sorted.size - 1)]


def _cdf_limits(xp, fp, x):
    """Left and right limits of the piecewise-linear CDF through
    ``(xp, fp)`` — duplicate ``xp`` entries form vertical jumps —
    evaluated at sorted points ``x``. Outside ``[xp[0], xp[-1]]`` the
    CDF is 0 / 1 (the conventions of the pre-round-4 ``np.interp``
    evaluation, which this generalizes: with strictly increasing ``xp``
    both limits reduce to ``np.interp(x, xp, fp, left=0, right=1)``)."""
    E = xp.size
    iL = np.searchsorted(xp, x, side="left")
    iR = np.searchsorted(xp, x, side="right")
    present = iR > iL
    lo = np.clip(iR - 1, 0, E - 1)
    hi = np.clip(iR, 0, E - 1)
    x0, x1, y0, y1 = xp[lo], xp[hi], fp[lo], fp[hi]
    with np.errstate(invalid="ignore"):   # inf - inf at sentinel runs
        denom = x1 - x0
        ok = denom > 0
        t = np.where(ok, (x - x0) / np.where(ok, denom, 1.0), 0.0)
        # a segment anchored at -inf spans infinitely far left: every
        # finite x sits at its right end (inf/inf -> NaN otherwise)
        t = np.where(np.isnan(t), np.where(np.isneginf(x0), 1.0, 0.0), t)
    interp = y0 + t * (y1 - y0)
    interp = np.where(iR == 0, 0.0, np.where(iR == E, 1.0, interp))
    left = np.where(present, fp[np.clip(iL, 0, E - 1)], interp)
    right = np.where(present, fp[np.clip(iR - 1, 0, E - 1)], interp)
    return left, right


class QuantileBinner:
    """Per-feature quantile binning into ``n_bins`` buckets.

    fit: edges[f, j] = the (j+1)/Q quantile of feature f over Q-1
    internal edges, where Q = n_bins normally and Q = n_bins - 1 under
    ``missing_bucket`` (one bucket is reserved, see below).
    transform: bin = number of edges <= x — in [0, n_bins) normally,
    shifted to [1, n_bins) under ``missing_bucket``.

    ``missing_bucket=True`` RESERVES bin 0 for missing values: finite
    values bin into [1, B) over B-2 internal edges and NaN maps to
    exactly bin 0 — the convention ``GBDTConfig(missing_bin=True)``
    expects for learned-default-direction routing. (The default mode
    also sends NaN to bin 0, but shares it with the lowest quantile.)
    """

    def __init__(self, n_bins: int = 256, missing_bucket: bool = False):
        lo = 3 if missing_bucket else 2   # the bucket consumes one bin;
        if not lo <= n_bins <= 65536:     # 2 would leave zero edges
            raise Mp4jError(
                f"n_bins must be in [{lo}, 65536]"
                f"{' with missing_bucket' if missing_bucket else ''}, "
                f"got {n_bins}")
        self.n_bins = n_bins
        self.missing_bucket = missing_bucket
        # [F, B-1] f32 ([F, B-2] under missing_bucket)
        self.edges: np.ndarray | None = None

    def fit(self, X, sample: int | None = 1_000_000, seed: int = 0,
            sample_weight=None):
        """Fit per-feature quantile edges from (a row sample of) X, a
        host array, on the host (:meth:`fit_staged` fits the same edges
        on a table that rests on a mesh).

        The rows are ``np.random.default_rng(seed).choice(N, sample,
        replace=False)`` where X has more than ``sample``.
        Missing values (NaN) are ignored when computing quantiles; at
        transform time they land in bin 0 (the missing bucket — every
        ``x >= edge`` comparison is False). A feature with no finite
        values at all cannot be binned and raises.

        ``sample_weight`` ([N] >= 0, optional — ytk-learn's instance
        weights): edges become WEIGHTED quantiles (inverted-CDF
        convention, matching ``np.quantile(method="inverted_cdf",
        weights=...)``; integer weights bin exactly like row
        duplication). ``None`` keeps the round-4 unweighted path
        bit-for-bit (numpy's default linear interpolation)."""
        X = np.asarray(X, np.float32)
        if X.ndim != 2:
            raise Mp4jError(f"X must be [N, F], got {X.shape}")
        sw = (None if sample_weight is None
              else _check_weights(sample_weight, X.shape[0]))
        idx = _sample_rows(X.shape[0], sample, seed)
        if idx is not None:
            X = X[idx]
            if sw is not None:
                sw = sw[idx]   # uniform row sample keeps weights unbiased
        # a feature must have at least one finite value (of positive
        # weight, when weighted); inf sentinels are fine (they produce
        # inf edges, which compare like any other value at transform
        # time and land inf samples in the top bins)
        evid = (np.isfinite(X) if sw is None
                else np.isfinite(X) & (sw[:, None] > 0))
        _refuse_unbinnable(~evid.any(axis=0),
                           "" if sw is None else " (zero-weight rows "
                           "carry no evidence)")
        nb = self._quantiles()
        qs = np.arange(1, nb) / nb
        if sw is not None:
            edges = np.empty((X.shape[1], nb - 1), np.float32)
            for f in range(X.shape[1]):
                v, cw = _sorted_weighted_col(X[:, f], sw)
                edges[f] = _wq_inverted_cdf(v, cw, qs)
            # inverted_cdf picks actual data values — no inf-inf
            # interpolation, so no NaN repair is needed
            self.edges = edges
            return self
        with warnings.catch_warnings():
            # inf sentinels make nanquantile warn on inf-inf interpolation
            warnings.simplefilter("ignore", RuntimeWarning)
            edges = np.nanquantile(X, qs, axis=0).T.astype(np.float32)
        # quantiles straddling inf sentinels interpolate to NaN; an
        # edge of +inf keeps the edge vector ordered and is matched
        # only by x = +inf (x >= inf), which belongs in the top bins
        self.edges = np.where(np.isnan(edges), np.float32(np.inf), edges)
        return self

    def local_sketch(self, X_shard, sample: int | None = 1_000_000,
                     seed: int = 0, sample_weight=None) -> FeatureSketch:
        """Per-rank half of the distributed fit: a :class:`FeatureSketch`
        with this shard's quantile points ``[min, q_{1/Q}, ...,
        q_{(Q-1)/Q}, max]`` ([F, Q+1]), merge-weight counts [F] (f32 —
        exact to 2**24 rows; beyond that the merge WEIGHT is
        approximate, which is harmless), finite-value evidence [F]
        (see ``merge_sketches``), and the per-point CDF ordinates
        [F, Q+1] — the grid for distinct data, true empirical jumps at
        tied points (see :class:`FeatureSketch`). A feature with no
        data on this shard yields NaN sketch rows and count 0 — legal
        locally, resolved at merge (another rank may hold its data).

        ``sample_weight`` ([N] >= 0, optional): quantile points become
        weighted quantiles (see :meth:`fit`), merge counts become
        per-feature WEIGHT totals (the [R, F] counts stack already IS
        the merge's weight vector, so weighted shards pool correctly
        with no wire-format change), and the CDF ordinates carry the
        weighted empirical limits at every point — ties and skewed
        weights ride the merge at their true mass."""
        X = np.asarray(X_shard, np.float32)
        if X.ndim != 2:
            raise Mp4jError(f"X must be [N, F], got {X.shape}")
        sw = (None if sample_weight is None
              else _check_weights(sample_weight, X.shape[0]))
        # merge weight = the FULL shard's data count / weight total
        # (NaN = missing is excluded; inf sentinels are data, exactly
        # as in fit) — it must be taken before sampling, or a 10M-row
        # shard sampled to 1M would weigh the same as a true 1M-row
        # shard in the merge
        if sw is None:
            counts = (~np.isnan(X)).sum(axis=0).astype(np.float32)
        else:
            counts = ((~np.isnan(X)) * sw[:, None]).sum(
                axis=0).astype(np.float32)
        idx = _sample_rows(X.shape[0], sample, seed)
        if idx is not None:
            X = X[idx]
            if sw is not None:
                sw = sw[idx]
        if sw is not None:
            return self._weighted_sketch(X, sw, counts)
        # evidence comes from the rows actually sketched, mirroring
        # fit()'s sample-then-check order: if sampling dropped every
        # data row of a feature, the sketch row is all-NaN and must
        # carry no weight either, or it would feed NaN into the merge
        finite = np.isfinite(X).any(axis=0).astype(np.float32)
        counts = np.where((~np.isnan(X)).any(axis=0), counts,
                          np.float32(0.0))
        nb = self._quantiles()
        qs = np.arange(1, nb) / nb
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            inner = np.nanquantile(X, qs, axis=0).T
            lo = np.nanmin(X, axis=0)
            hi = np.nanmax(X, axis=0)
        # same inf rule as fit(): quantiles straddling inf sentinels
        # interpolate to NaN; +inf keeps the sketch monotone (hi
        # includes the inf itself, so [.., inf, .., inf] stays ordered)
        inner = np.where(np.isnan(inner), np.inf, inner)
        sketch = np.concatenate(
            [lo[:, None], inner, hi[:, None]], axis=1).astype(np.float32)
        # CDF ordinates: grid everywhere, EXCEPT runs of tied sketch
        # values, which are widened to the shard's true empirical jump
        # — [frac < v, frac <= v] — so a value holding (say) 40% of the
        # mass carries 40% through the merge instead of the <= 1/Q the
        # grid can express. Distinct-valued data keeps the exact grid,
        # preserving the merge's single-rank exactness.
        E = sketch.shape[1]
        grid = (np.arange(E) / nb).astype(np.float32)
        cdfs = np.tile(grid, (X.shape[1], 1))
        for f in range(X.shape[1]):
            row = sketch[f]
            if np.isnan(row).any() or not (row[1:] == row[:-1]).any():
                continue
            col = X[:, f]
            col = np.sort(col[~np.isnan(col)])
            M = col.size
            j = 0
            while j < E:
                k = j
                while k + 1 < E and row[k + 1] == row[j]:
                    k += 1
                if k > j:
                    left = np.searchsorted(col, row[j], side="left") / M
                    right = np.searchsorted(col, row[j],
                                            side="right") / M
                    a = min(grid[j], left)
                    b = max(grid[k], right)
                    cdfs[f, j:k + 1] = np.linspace(a, b, k - j + 1)
                j = k + 1
            cdfs[f] = np.maximum.accumulate(np.clip(cdfs[f], 0.0, 1.0))
        # a shard whose feature is all-NaN contributes a NaN sketch row
        # with count 0 — merge_sketches skips it by the count
        return FeatureSketch(sketch, counts, finite, cdfs)

    def _weighted_sketch(self, X, sw, counts) -> FeatureSketch:
        """Weighted :meth:`local_sketch` body: per-feature weighted
        quantile points + weighted empirical CDF ordinates. For
        distinct-valued data the ordinates land exactly on the grid
        (each inverted-CDF point v_q satisfies F_left < q <= F_right),
        so the merge's single-rank inversion reproduces the weighted
        fit at every grid quantile; tied runs are widened to their true
        weighted jump, like the unweighted path."""
        F = X.shape[1]
        nb = self._quantiles()
        E = nb + 1
        qs = np.arange(1, nb) / nb
        grid = np.arange(E) / nb
        sketch = np.full((F, E), np.nan, np.float32)
        cdfs = np.tile(grid.astype(np.float32), (F, 1))
        finite = np.zeros(F, np.float32)
        counts = counts.astype(np.float32).copy()
        for f in range(F):
            v, cw = _sorted_weighted_col(X[:, f], sw)
            if v is None:
                # sampling (or zero weights) left no data: the sketch
                # row must carry no merge weight, like the unweighted
                # sample-then-check order
                counts[f] = 0.0
                continue
            finite[f] = float(np.isfinite(v).any())
            inner = _wq_inverted_cdf(v, cw, qs)
            row = np.concatenate([[v[0]], inner,
                                  [v[-1]]]).astype(np.float32)
            sketch[f] = row
            W = cw[-1]
            cw0 = np.concatenate([[0.0], cw])
            left = cw0[np.searchsorted(v, row, side="left")] / W
            right = cw0[np.searchsorted(v, row, side="right")] / W
            out = np.empty(E)
            j = 0
            while j < E:
                k = j
                while k + 1 < E and row[k + 1] == row[j]:
                    k += 1
                if k > j:
                    a = min(grid[j], left[j])
                    b = max(grid[k], right[j])
                    out[j:k + 1] = np.linspace(a, b, k - j + 1)
                else:
                    out[j] = np.clip(grid[j], left[j], right[j])
                j = k + 1
            cdfs[f] = np.maximum.accumulate(np.clip(out, 0.0, 1.0))
        return FeatureSketch(sketch, counts, finite, cdfs)

    def merge_sketches(self, sketch_stack, counts_stack,
                       finite_stack=None, cdf_stack=None):
        """Merge per-rank sketches into fitted edges (identical on
        every caller). Each rank's sketch is a piecewise-linear CDF
        through its (value, cdf) points — the grid [0, 1/Q, ..., 1]
        when ``cdf_stack`` is omitted, the tie-aware ordinates of
        :class:`FeatureSketch` when given. The pooled CDF is the
        count-weighted average of the per-rank CDFs, evaluated (left
        AND right limits, so tied-value jumps survive pooling) at the
        union of all sketch values and inverted at the target
        quantiles. Guarantees: exact when one rank holds all of a
        feature's distinct-valued data; O(1/Q) in quantile space across
        ranks for continuous data; and — with ``cdf_stack`` — a value
        carrying mass >= 2/Q on some shard appears as a tied run whose
        TRUE mass rides the merge, so heavy ties no longer collapse to
        grid resolution (a target quantile landing strictly inside a
        pooled jump inverts to exactly that tied value, as
        ``np.nanquantile`` on the pooled data does; property-tested
        under 90%-mass-in-5-values in tests/test_binning.py). Edges
        stay monotone and inside [min, max].
        [R, F, Q+1] sketches + [R, F] counts (+ [R, F, Q+1] cdf) ->
        self fitted.

        ``finite_stack`` ([R, F], optional): per-rank does-this-feature-
        have-any-FINITE-value evidence. ``fit()`` refuses a feature with
        no finite values (all-NaN or all-±inf); when the stack is given
        (``fit_distributed`` ships it alongside the sketches) the merge
        raises under the same condition instead of silently emitting
        all-inf edges (ADVICE round 3). It is deliberately separate from
        the merge WEIGHT: an inf-only shard still carries its inf mass
        into the pooled CDF — exactly as its rows would in a single-host
        ``fit`` — it just cannot by itself testify that the feature is
        binnable."""
        sketch_stack = np.asarray(sketch_stack, np.float32)
        counts_stack = np.asarray(counts_stack, np.float32)
        R, F, E = sketch_stack.shape
        nb = self._quantiles()
        if E != nb + 1:
            raise Mp4jError(
                f"sketch has {E} points per feature; this binner needs "
                f"{nb + 1} (n_bins mismatch?)")
        no_data = (counts_stack <= 0).all(axis=0)
        if no_data.any():
            raise Mp4jError(
                f"features {np.flatnonzero(no_data).tolist()} have no "
                "non-missing values on any rank")
        if finite_stack is not None:
            no_finite = (np.asarray(finite_stack, np.float32)
                         <= 0).all(axis=0)
            if no_finite.any():
                raise Mp4jError(
                    f"features {np.flatnonzero(no_finite).tolist()} "
                    "have no finite values on any rank (all NaN/inf); "
                    "fit() refuses these too")
        grid = np.arange(E) / nb                     # [0, 1/Q, ..., 1]
        if cdf_stack is None:
            cdf_stack = np.broadcast_to(grid, sketch_stack.shape)
        else:
            cdf_stack = np.asarray(cdf_stack)
            if cdf_stack.shape != sketch_stack.shape:
                raise Mp4jError(
                    f"cdf stack shape {cdf_stack.shape} != sketch "
                    f"shape {sketch_stack.shape}")
            # ordinates ride the wire as float32; snap grid knots back
            # to their exact float64 values so the distinct-data
            # inversion stays bit-exact against fit() (f32(0.9) =
            # 0.90000004 would otherwise shift every inversion knot)
            g32 = grid.astype(np.float32)
            cdf_stack = np.where(
                cdf_stack.astype(np.float32) == g32,
                grid, cdf_stack.astype(np.float64))
        qs = grid[1:-1]
        merged = np.empty((F, nb - 1), np.float32)
        for f in range(F):
            live = counts_stack[:, f] > 0
            w = counts_stack[live, f]
            w = w / w.sum()
            # pooled CDF limits at every distinct sketch value: the
            # count-weighted average of the per-rank CDFs' left/right
            # limits (jumps at tied points survive pooling)
            pts = np.unique(sketch_stack[live, f])
            pl = np.zeros(pts.shape)
            pr = np.zeros(pts.shape)
            for r_w, r_sk, r_cdf in zip(w, sketch_stack[live, f],
                                        cdf_stack[live, f]):
                lt, rt = _cdf_limits(r_sk, r_cdf, pts)
                pl += r_w * lt
                pr += r_w * rt
            # inversion polyline: (left, v), (right, v) per value —
            # vertical jump segments invert to exactly v
            inv_x = np.empty(2 * pts.size)
            inv_x[0::2] = pl
            inv_x[1::2] = pr
            merged[f] = np.interp(qs, inv_x, np.repeat(pts, 2))
        self.edges = np.where(np.isnan(merged), np.float32(np.inf),
                              merged)
        return self

    def fit_distributed(self, X_shard, comm,
                        sample: int | None = 1_000_000, seed: int = 0,
                        sample_weight=None):
        """SPMD distributed fit: every rank calls this with ITS OWN
        shard and an mp4j comm exposing ``rank`` / ``slave_num`` /
        ``allgather_array`` (socket, thread, and jax.distributed
        backends all do). One fixed-size allgather moves the sketches;
        raw features never leave their rank. All ranks return fitted
        with identical edges.

        Each rank's wire segment leads with a (n_bins, missing_bucket,
        F) header, validated after the allgather: a binner-config or
        feature-count mismatch across ranks would otherwise garble the
        merge silently (or shear the flat buffer into misaligned
        segments).

        ``sample_weight`` weighs THIS RANK's rows (see
        :meth:`local_sketch`); the merge pools weighted and unweighted
        shards through the same counts vector."""
        from ytk_mp4j_tpu.operands import Operands

        edges, counts, finite, cdfs = self.local_sketch(
            X_shard, sample, seed, sample_weight=sample_weight)
        F, E = edges.shape
        n, r = comm.slave_num, comm.rank
        hdr = np.asarray(
            [self.n_bins, int(self.missing_bucket), F], np.float32)
        H = len(hdr)
        seg = H + 2 * F * E + 2 * F
        # segment length is itself config-dependent (F, E); a mismatch
        # would shear the main allgather into misaligned blocks before
        # any header could be read, so sizes are exchanged first
        sizes = np.zeros(n, np.float32)
        sizes[r] = seg
        comm.allgather_array(sizes, Operands.FLOAT)
        if not (sizes == seg).all():
            raise Mp4jError(
                f"fit_distributed sketch-size mismatch across ranks: "
                f"{sizes.astype(int).tolist()} (n_bins / missing_bucket "
                f"/ feature-count differ)")
        buf = np.zeros(n * seg, np.float32)
        s = r * seg
        o0, o1 = H, H + F * E               # values
        o2 = o1 + F * E                      # cdf ordinates
        o3, o4 = o2 + F, o2 + 2 * F          # counts | finite
        buf[s: s + H] = hdr
        buf[s + o0: s + o1] = edges.ravel()
        buf[s + o1: s + o2] = cdfs.ravel()
        buf[s + o2: s + o3] = counts
        buf[s + o3: s + o4] = finite
        comm.allgather_array(buf, Operands.FLOAT)
        rows = buf.reshape(n, seg)
        for p in range(n):
            if not np.array_equal(rows[p, :H], hdr):
                raise Mp4jError(
                    f"fit_distributed config mismatch: rank {p} sent "
                    f"(n_bins, missing_bucket, F) = "
                    f"{rows[p, :H].astype(int).tolist()}, this rank has "
                    f"{hdr.astype(int).tolist()}")
        return self.merge_sketches(
            rows[:, o0:o1].reshape(n, F, E),
            rows[:, o2:o3],
            rows[:, o3:o4],
            cdf_stack=rows[:, o1:o2].reshape(n, F, E))

    def _quantiles(self) -> int:
        """Q: the edges are the i/Q quantiles, i = 1 .. Q - 1."""
        return self.n_bins - 1 if self.missing_bucket else self.n_bins

    def fit_staged(self, table, n_rows: int,
                   sample: int | None = 1_000_000, seed: int = 0):
        """:meth:`fit` (unweighted) of a float table that already rests
        on a mesh: ``table`` [n_shards, rows a shard, F] f32, rows
        sharded, NaN where a cell is empty, of which the first
        ``n_rows`` (in shard order) are the data and the rest padding.
        The rows are the ones ``fit`` samples and the edges are the f32
        numbers ``fit`` gives for the host array, to the bit.

        The device does the sorting (``_sketch``: the sample's rows
        picked where they rest, every column sorted with NaN last, the
        order statistics round every quantile read out); the host waits
        for those picks (``mp4j.bin.device_wait``), [F, Q - 1] triples,
        and interpolates them as numpy does (``_edges_of``)."""
        if table.ndim != 3 or table.dtype != np.float32:
            raise Mp4jError(f"table must be f32 [n_shards, rows, F], got "
                            f"{table.dtype} {table.shape}")
        rows = table.shape[0] * table.shape[1]
        idx = _sample_rows(n_rows, sample, seed)
        with spans.span("mp4j.bin.fit", columns=table.shape[2],
                        sample_rows=n_rows if idx is None else len(idx),
                        blocks=-(-table.shape[2] // min(
                            table.shape[2], _SKETCH_COLUMNS))):
            keep = np.zeros(rows, bool)
            keep[slice(n_rows) if idx is None else idx] = True
            found = _sketch_program(self._quantiles(), table.sharding)(
                table, keep)
            with spans.span("mp4j.bin.device_wait"):
                picks, n, finite = jax.device_get(found)
            _refuse_unbinnable(~finite)
            self.edges = _edges_of(picks, n, self._quantiles())
        return self

    # Bytes of a host table that cross at a time in ``transform``: the
    # search holds nothing of [rows, F, edges], so a chunk is sized by
    # the link and not by the number of edges (the guard it replaces cut
    # 968 columns into chunks of 272 rows).
    _TRANSFORM_CHUNK_BYTES = 64 << 20

    def transform(self, X) -> np.ndarray:
        """Continuous [N, F] -> int32 bin ids in [0, n_bins), host array
        in and host array out: for a caller who wants the bins (and
        ``fit_transform``). No trainer path calls it:
        :meth:`transform_staged` bins a table that rests on a mesh and
        leaves the bins there, and ``GBDTTrainer.predict_raw_chunks`` /
        ``predict_raw`` bin inside their scoring program.

        NaN inputs land in bin 0 (the missing bucket; see fit) — this
        deliberately diverges from ``np.searchsorted``, which sorts NaN
        after every edge. Under ``missing_bucket`` finite values land
        in [1, n_bins) and bin 0 is EXACTLY the NaN set.

        The rows go through the device's search (``_count_edges``,
        the program ``transform_staged`` runs) in chunks of
        ``_TRANSFORM_CHUNK_BYTES``, one dispatch where the table is
        smaller; a chunk's bins are fetched while the next chunk is
        binned."""
        X = np.asarray(X, np.float32)
        self._check_width(X.shape, X.ndim == 2)
        program = _transform_program(bool(self.missing_bucket),
                                     self.edges.shape[1])
        edges = jnp.asarray(self.edges)
        rows = max(1, self._TRANSFORM_CHUNK_BYTES // (4 * X.shape[1]))
        if X.shape[0] <= rows:
            return np.asarray(program(jnp.asarray(X), edges))
        out = np.empty(X.shape, np.int32)
        binned = None
        for s in range(0, X.shape[0], rows):
            # the last chunk is as long as the others (one program): it
            # starts early and bins rows again
            s = min(s, X.shape[0] - rows)
            launched = s, program(jnp.asarray(X[s:s + rows]), edges)
            if binned is not None:
                out[binned[0]:binned[0] + rows] = binned[1]
            binned = launched
        out[binned[0]:binned[0] + rows] = binned[1]
        return out

    def transform_staged(self, table):
        """:meth:`transform` of a float table that rests on a device or
        a mesh ([N, F], or [n_shards, rows a shard, F] with its rows
        sharded) into int32 bins that rest there likewise: same shape,
        same sharding, and the layout a placed host array has, which is
        what ``GBDTTrainer``'s step takes from ``shard_bins``. One
        program over the whole table (on a TPU one ``mp4j_bin`` kernel,
        whose grid is the loop over its blocks), every member binning
        its own rows, launched and not waited for; nothing visits the
        host. The span says how many compares a cell the program issues
        (``compares``: one a level of the search, 8 at 254 edges)."""
        self._check_width(table.shape, table.ndim in (2, 3))
        with spans.span("mp4j.bin.transform", columns=table.shape[-1],
                        rows=int(np.prod(table.shape[:-1])),
                        compares=bin_kernel.search_steps(
                            self.edges.shape[1])):
            return _transform_program(
                bool(self.missing_bucket), self.edges.shape[1],
                table.sharding if table.ndim == 3 else None)(
                    table, self.edges)

    def _check_width(self, shape, ranked: bool) -> None:
        if self.edges is None:
            raise Mp4jError("binner is not fitted")
        if not ranked or shape[-1] != self.edges.shape[0]:
            raise Mp4jError(
                f"X must be [N, {self.edges.shape[0]}], got {shape}")

    def fit_transform(self, X, **kw) -> np.ndarray:
        return self.fit(X, **kw).transform(X)


def _sample_rows(n_rows: int, sample: int | None, seed: int):
    """The rows a fit looks at: ``sample`` of them drawn without
    replacement from ``default_rng(seed)``, or None for all of them."""
    if sample is None or n_rows <= sample:
        return None
    return np.random.default_rng(seed).choice(n_rows, sample, replace=False)


def _refuse_unbinnable(bad: np.ndarray, why: str = "") -> None:
    """A feature must have at least one finite value (of positive
    weight, when weighted); inf sentinels beside it are fine (they
    produce inf edges, which compare like any other value at transform
    time and land inf samples in the top bins)."""
    if bad.any():
        raise Mp4jError(
            f"features {np.flatnonzero(bad).tolist()} have no "
            f"finite values to fit quantile edges from{why}")


def _whole(sharding):
    """The sharding that holds an array whole on every device of
    ``sharding``'s mesh."""
    return jax.sharding.NamedSharding(sharding.mesh,
                                      jax.sharding.PartitionSpec())


# Columns whose sample is sorted at a time. A staged table rests with
# its rows along the lanes and eight columns to a tile's sublanes, and a
# block of eight is sorted as it rests; a wider block is first copied so
# that its columns lie along the lanes. The sorts of 1,183,747 rows x
# 968 columns, in s (ledger notes of PR 43, whose chip runs these were):
# 2.287 at 128 columns a block, 1.822 at 64, 1.781 at 32, 1.773 at 16,
# 1.449 at 8. A block and its sorted copy are all the device holds
# beside the table (76 MB at that size).
_SKETCH_COLUMNS = 8


def _sketch(sample, nb: int, keep):
    """The order statistics the edges of ``sample`` [S, F] f32 lie
    between. A row that ``keep`` [S] does not mark is a row of NaN to
    the sketch (cheaper than taking the marked rows out of a table that
    rests with its rows along the lanes); NaN sorts last, so a column's
    n values are its first n order statistics. Returns ``picks`` [3, F,
    nb - 1] f32, ``n`` [F] (the column's values that are not NaN) and
    ``finite`` [F] bool (whether it holds a finite value).

    The i/nb quantile, i = 1 .. nb - 1, sits at ``i (n - 1) / nb = k +
    r / nb`` among the sorted values; k is found by integer arithmetic
    with ``n - 1 = nb a + b`` (f32 cannot hold the position at a million
    rows, and ``i (n - 1)`` passes 2**31 at nine million). numpy floors
    the float64 product ``(n - 1) * (i / nb)``, which is k or, where the
    product should be whole and comes out a hair under, k - 1; so the
    picks are the values at k - 1, k and k + 1 (clipped to the column),
    and ``_edges_of`` takes numpy's two of the three.

    The columns are taken ``_SKETCH_COLUMNS`` at a time in a loop, so
    that one block and its sorted copy are all the device holds beside
    the table; the last block starts early where the width is no whole
    number of blocks, and finds the same values again."""
    rows, width = sample.shape
    columns = min(width, _SKETCH_COLUMNS)
    i = jnp.arange(1, nb, dtype=jnp.uint32)[:, None]

    def block_values(c, found):
        start = jnp.minimum(c * columns, width - columns)
        with jax.named_scope("bin.sketch.gather"):
            block = jax.lax.dynamic_slice(sample, (0, start),
                                          (rows, columns))
            block = jnp.where(keep[:, None], block, jnp.nan)
            n = (~jnp.isnan(block)).sum(axis=0, dtype=jnp.uint32)
            finite = jnp.isfinite(block).any(axis=0)
        with jax.named_scope("bin.sketch.sort"):
            ordered = jnp.sort(block, axis=0, stable=False)  # no index rides
        with jax.named_scope("bin.sketch.edges"):
            last = jnp.maximum(n, 1)[None, :] - 1
            k = i * (last // nb) + i * (last % nb) // nb
            at = jnp.stack([jnp.maximum(k, 1) - 1, k,
                            jnp.minimum(k + 1, last)]).astype(jnp.int32)
            picks = jnp.take_along_axis(ordered[None], at, axis=1)
        update = jax.lax.dynamic_update_slice_in_dim
        return (update(found[0], picks.transpose(0, 2, 1), start, axis=1),
                update(found[1], n, start, axis=0),
                update(found[2], finite, start, axis=0))

    return jax.lax.fori_loop(
        0, -(-width // columns), block_values,
        (jnp.zeros((3, width, nb - 1), jnp.float32),
         jnp.zeros(width, jnp.uint32), jnp.zeros(width, bool)))


def _edges_of(picks, n, nb: int) -> np.ndarray:
    """The edges [F, nb - 1] f32 from ``_sketch``'s picks, on the host,
    as ``np.nanquantile(X, i / nb)`` takes them from f32 values, to the
    bit: the position ``(n - 1) * (i / nb)`` in float64 and its floor,
    the difference of the two neighbours in f32, the step in float64
    from the nearer end (numpy's ``_lerp``), rounded to f32 once. 968 x
    254 triples cost the host a millisecond; on the device the same in
    f32 is three roundings, 2.2 ulps of the larger neighbour where the
    two have opposite signs, and its division is not IEEE's."""
    n = np.maximum(n.astype(np.int64), 1)[:, None]
    i = np.arange(1, nb, dtype=np.int64)
    at = (n - 1) * (i / nb)                     # numpy's virtual index
    below = np.floor(at).astype(np.int64)
    k = i * (n - 1) // nb                       # the device's
    if not ((below == k) | (below == k - 1)).all():
        raise Mp4jError("a quantile's position left the picked values")
    first = np.where(below == k, 1, 0)[None]    # picks hold k-1, k, k+1
    lo = np.take_along_axis(picks, first, axis=0)[0]
    hi = np.take_along_axis(picks, first + 1, axis=0)[0]
    t = at - below
    with np.errstate(invalid="ignore"):     # inf - inf at sentinel runs
        step = hi - lo                      # f32, as numpy subtracts
        edges = np.where(t >= 0.5, hi - step * (1 - t), lo + step * t)
    # quantiles straddling inf sentinels interpolate to NaN; an edge of
    # +inf keeps the edge vector ordered and is matched only by x = +inf
    # (x >= inf), which belongs in the top bins
    return np.where(np.isnan(edges), np.inf, edges).astype(np.float32)


@lru_cache(maxsize=None)
def _sketch_program(nb: int, sharding):
    """The jitted sketch of the rows that ``keep`` [rows] marks of a
    staged table [n_shards, rows a shard, F] (``sharding``: the
    table's); the picks are whole on every device of its mesh."""
    def program(table, keep):
        return _sketch(table.reshape((-1, table.shape[-1])), nb, keep)

    with spans.span("mp4j.step.build", key="bin_sketch", quantiles=nb,
                    columns=_SKETCH_COLUMNS):
        return jax.jit(program, out_shardings=_whole(sharding))


def _kernel_compiles() -> bool:
    """Whether ``_count_edges`` is being built for a TPU, where its
    search is the Mosaic kernel (a test that compiles for a described
    chip answers for it)."""
    return jax.default_backend() == "tpu"


def _count_edges(X, edges, shift: bool):
    """bin = #edges <= x for every cell of ``X`` [..., F] against its
    column's ``edges`` [F, E], by upper-bound search: the edges sorted
    here and ``bin_kernel.search_steps(E)`` probes a cell (8 at 254
    edges). On a TPU one ``mp4j_bin`` kernel over the table as it rests
    (``ops/bin_kernel.py``: the probe is a gather inside a vector
    register, off the serial gather unit XLA's searchsorted would use);
    elsewhere the same walk in ``jnp`` (interpreted Pallas inside a
    ``shard_map`` trips ``check_vma``). With ``shift`` (the reserved
    missing bucket) finite values move up to [1, B) and NaN, for which
    every comparison is False, stays the SOLE occupant of bin 0."""
    if _kernel_compiles():
        return bin_kernel.pallas_bin_counts(
            X.reshape((-1, X.shape[-1])), edges, shift).reshape(X.shape)
    nodes = bin_kernel.search_table(edges).T
    column = jnp.arange(X.shape[-1])
    return bin_kernel.upper_bound(
        X, lambda k, i, went: nodes[i, column],
        bin_kernel.search_steps(edges.shape[1]), shift)


@lru_cache(maxsize=None)
def _transform_program(shift: bool, n_edges: int, sharding=None):
    """The jitted transform by ``n_edges`` edges a column, of a table on
    a device or (``sharding`` given) of a staged one, every member
    binning its own rows; the bins rest as the floats did. The build
    span says which block of the table the TPU's kernel takes at a
    time."""
    def program(X, edges):
        with jax.named_scope("bin.transform"):
            return _count_edges(X, edges, shift)

    columns, rows = bin_kernel.bin_blocks(n_edges)
    with spans.span("mp4j.step.build", key="bin_transform", shift=shift,
                    bin_block_columns=columns, bin_block_rows=rows):
        if sharding is None:
            return jax.jit(program)
        return jax.jit(jax.shard_map(
            program, mesh=sharding.mesh,
            in_specs=(sharding.spec, jax.sharding.PartitionSpec()),
            out_specs=sharding.spec))
