"""Quantile binning front end (models/binning.py) + GBDTTrainer.predict:
the continuous-features -> bins -> train -> predict consumer flow."""

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models.binning import QuantileBinner
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.parallel import make_mesh


def test_bins_match_searchsorted(rng):
    N, F, B = 5000, 4, 16
    X = rng.standard_normal((N, F)).astype(np.float32) * [1, 10, 0.1, 3]
    bins = QuantileBinner(B).fit_transform(X, sample=None)
    assert bins.dtype == np.int32
    assert bins.min() >= 0 and bins.max() < B
    binner = QuantileBinner(B).fit(X, sample=None)
    for f in range(F):
        want = np.searchsorted(binner.edges[f], X[:, f], side="right")
        np.testing.assert_array_equal(bins[:, f], want)


def test_bins_are_balanced(rng):
    N, B = 20_000, 8
    X = rng.standard_normal((N, 1)).astype(np.float32)
    bins = QuantileBinner(B).fit_transform(X, sample=None)
    counts = np.bincount(bins[:, 0], minlength=B)
    # quantile edges -> each bucket holds ~N/B
    assert counts.min() > 0.8 * N / B
    assert counts.max() < 1.2 * N / B


def test_errors():
    with pytest.raises(Mp4jError):
        QuantileBinner(1)
    b = QuantileBinner(4)
    with pytest.raises(Mp4jError):
        b.transform(np.zeros((3, 2)))          # not fitted
    b.fit(np.random.default_rng(0).random((100, 2)), sample=None)
    with pytest.raises(Mp4jError):
        b.transform(np.zeros((3, 5)))          # wrong F


def test_nan_handling(rng):
    """NaN rows land in bin 0 (missing bucket), edges fit from finite
    values only, and an all-NaN feature raises."""
    N, B = 4000, 8
    X = rng.standard_normal((N, 2)).astype(np.float32)
    X[::7, 0] = np.nan
    b = QuantileBinner(B).fit(X, sample=None)
    clean = QuantileBinner(B).fit(X[np.isfinite(X[:, 0])], sample=None)
    np.testing.assert_allclose(b.edges[0], clean.edges[0], rtol=1e-6)
    bins = b.transform(X)
    assert (bins[::7, 0] == 0).all()
    assert bins.min() >= 0 and bins.max() < B
    X_bad = X.copy()
    X_bad[:, 1] = np.nan
    with pytest.raises(Mp4jError):
        QuantileBinner(B).fit(X_bad, sample=None)
    # inf sentinels are legal: they fit fine and bin to the top bucket
    X_inf = rng.standard_normal((N, 1)).astype(np.float32)
    X_inf[::3, 0] = np.inf
    bi = QuantileBinner(B).fit(X_inf, sample=None)
    out = bi.transform(X_inf)
    assert (out[::3, 0] == B - 1).all()


def test_save_load_exact_path(rng, tmp_path):
    """save_model must honor the exact path (np.savez normally appends
    .npz) and load_model must rebuild the binner's true granularity."""
    N, F = 200, 3
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = X[:, 0].astype(np.float32)
    binner = QuantileBinner(8).fit(X, sample=None)   # coarser than n_bins
    cfg = GBDTConfig(n_features=F, n_bins=32, depth=2, n_trees=2)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees, _ = tr.train(binner.transform(X), y)
    path = str(tmp_path / "model.bin")               # no .npz suffix
    tr.save_model(path, trees, binner=binner)
    cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
    assert binner2.n_bins == 8
    np.testing.assert_allclose(binner2.edges, binner.edges)


def test_predict_proba_extreme_margins_no_overflow(rng):
    """Confidently-signed margins must not overflow the sigmoid."""
    F, B = 2, 4
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=1, n_trees=1,
                     learning_rate=1000.0, loss="logistic")
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    # a tree whose leaves are huge margins
    trees = [(np.zeros(1, np.int32), np.zeros(1, np.int32),
              np.zeros(1, np.int32),
              np.array([-500.0, 500.0], np.float32))]
    bins = rng.integers(0, B, (64, F)).astype(np.int32)
    with np.errstate(over="raise"):
        p = tr.predict(bins, trees, proba=True)
    assert np.isfinite(p).all()
    assert ((p >= 0) & (p <= 1)).all()


def test_save_load_roundtrip(rng, tmp_path):
    """train -> save -> load in a fresh trainer -> identical preds on
    new continuous data (the train-then-serve flow)."""
    N, F, B = 1500, 4, 16
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (X[:, 2] + 0.1 * rng.standard_normal(N)).astype(np.float32)
    binner = QuantileBinner(B).fit(X, sample=None)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=4,
                     learning_rate=0.3)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, _ = tr.train(binner.transform(X), y)
    path = str(tmp_path / "model.npz")
    tr.save_model(path, trees, binner=binner)

    cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
    assert cfg2 == cfg
    X_new = rng.standard_normal((200, F)).astype(np.float32)
    serve = GBDTTrainer(cfg2, mesh=make_mesh(1))
    np.testing.assert_allclose(
        serve.predict(binner2.transform(X_new), trees2),
        tr.predict(binner.transform(X_new), trees),
        rtol=1e-6)


def test_continuous_end_to_end(rng):
    """The full ytk-learn-style consumer flow: continuous X -> quantile
    bins -> distributed GBDT -> ensemble predict reproduces the
    training-time predictions."""
    N, F, B = 2000, 5, 32
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (np.sin(3 * X[:, 0]) + 0.1 * rng.standard_normal(N)).astype(
        np.float32)
    bins = QuantileBinner(B).fit_transform(X, sample=None)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=4, learning_rate=0.3,
                     n_trees=5)
    tr = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, train_preds = tr.train(bins, y)
    mse = float(np.mean((train_preds[:N] - y) ** 2))
    assert mse < float(np.var(y)) * 0.5

    preds = tr.predict(bins, trees)
    np.testing.assert_allclose(preds, train_preds[:N], rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------------- distributed fit
def _quantile_positions(X, edges):
    """Empirical CDF position of each edge: |F_hat(edge) - target q|
    is the natural error metric for a quantile sketch."""
    F = X.shape[1]
    pos = np.empty_like(edges)
    for f in range(F):
        col = np.sort(X[:, f][np.isfinite(X[:, f])])
        pos[f] = np.searchsorted(col, edges[f], side="right") / len(col)
    return pos


def test_merge_sketches_matches_single_host(rng):
    """Weighted quantile-of-quantiles: merged edges must land within
    2/Q of the target quantile positions (documented tolerance; the
    approximation error is O(1/Q) in quantile space)."""
    N, F, B, R = 40_000, 5, 32, 4
    X = np.stack([
        rng.standard_normal(N),
        rng.lognormal(0.0, 1.0, N),
        rng.uniform(-5, 5, N),
        rng.standard_normal(N) * 100 + 7,
        np.where(rng.random(N) < 0.3, np.nan, rng.standard_normal(N)),
    ], axis=1).astype(np.float32)
    # unequal shard sizes
    cuts = [0, 4_000, 14_000, 27_000, N]
    shards = [X[cuts[i]:cuts[i + 1]] for i in range(R)]

    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    qs = np.arange(1, B) / B
    pos = _quantile_positions(X, b.edges)
    err = np.abs(pos - qs[None, :]).max()
    assert err < 2.0 / B, err
    # and the exact fit passes the same bar much more tightly
    exact = QuantileBinner(B).fit(X, sample=None)
    pos_e = _quantile_positions(X, exact.edges)
    assert np.abs(pos_e - qs[None, :]).max() < err


def test_merge_sketch_feature_missing_on_some_ranks(rng):
    """A feature with data on only one rank must still bin correctly:
    NaN sketches carry zero weight in the merge."""
    B, R = 8, 3
    col = rng.standard_normal(9_000).astype(np.float32)
    shards = []
    for r in range(R):
        s = np.empty((3_000, 2), np.float32)
        s[:, 0] = rng.standard_normal(3_000)
        s[:, 1] = np.nan if r != 1 else col[:3_000]
        shards.append(s)
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    # feature 1's edges come purely from rank 1's data
    want = QuantileBinner(B).fit(
        shards[1][:, 1:2], sample=None).edges[0]
    np.testing.assert_allclose(b.edges[1], want, rtol=1e-5, atol=1e-5)


def test_merge_sketch_no_data_anywhere_raises():
    b = QuantileBinner(4)            # Q+1 = 5 sketch points
    edges = np.full((2, 1, 5), np.nan, np.float32)
    counts = np.zeros((2, 1), np.float32)
    with pytest.raises(Mp4jError, match="no non-missing"):
        b.merge_sketches(edges, counts)


class _OneRankComm:
    """Minimal comm for exercising fit_distributed single-rank."""
    rank, slave_num = 0, 1

    def allgather_array(self, arr, operand=None, ranges=None):
        return arr


def test_all_inf_feature_raises_like_fit(rng):
    """fit() refuses a feature with no finite values; fit_distributed
    must agree instead of silently producing all-inf edges (ADVICE
    round 3): finite-value evidence rides the sketch wire and
    merge_sketches raises when no rank contributes any."""
    X = np.stack([rng.standard_normal(100).astype(np.float32),
                  np.full(100, np.inf, np.float32)], axis=1)
    with pytest.raises(Mp4jError, match="no finite"):
        QuantileBinner(8).fit(X, sample=None)
    with pytest.raises(Mp4jError, match="no finite"):
        QuantileBinner(8).fit_distributed(X, _OneRankComm(),
                                          sample=None)
    # the low-level merge enforces it whenever the evidence is supplied
    b = QuantileBinner(4)
    sk, c, fin, _ = b.local_sketch(np.full((10, 1), np.inf, np.float32),
                                sample=None)
    assert c[0] == 10          # inf is data: full merge weight kept
    assert fin[0] == 0.0       # ...but it is not finite evidence
    with pytest.raises(Mp4jError, match="no finite"):
        b.merge_sketches(sk[None], c[None], np.zeros((1, 1), np.float32))


def test_sampling_drops_all_finite_rows_still_raises():
    """If row sampling excludes every data row of a feature, the sketch
    is unusable and the distributed fit must refuse like fit() does —
    not silently emit all-inf edges or feed NaN sketch rows into the
    merge. The finite rows are placed OUTSIDE the known sample draw so
    the exclusion is deterministic."""
    N, S, seed = 10_000, 50, 0
    picked = set(np.random.default_rng(seed).choice(N, S, replace=False))
    free = [i for i in range(N) if i not in picked][:3]
    X = np.full((N, 2), np.nan, np.float32)
    X[:, 0] = np.random.default_rng(1).standard_normal(N)
    X[free, 1] = [1.0, 2.0, 3.0]          # data exists, sample misses it
    with pytest.raises(Mp4jError, match="no finite"):
        QuantileBinner(8).fit(X, sample=S, seed=seed)
    with pytest.raises(Mp4jError, match="no"):
        QuantileBinner(8).fit_distributed(X, _OneRankComm(),
                                          sample=S, seed=seed)
    # and the sketch itself reports the feature as weightless
    _, c, fin, _ = QuantileBinner(8).local_sketch(X, sample=S, seed=seed)
    assert c[1] == 0.0 and fin[1] == 0.0
    assert c[0] == N and fin[0] == 1.0


def test_mixed_inf_shard_keeps_inf_mass(rng):
    """An inf-only shard next to a finite shard must still contribute
    its inf mass to the pooled CDF (as its rows would in a single-host
    fit) — the finite-evidence check may not alter merge weights."""
    fin = rng.standard_normal((1000, 1)).astype(np.float32)
    inf = np.full((1000, 1), np.inf, np.float32)
    b = QuantileBinner(8)
    edges = b.fit_distributed(
        np.concatenate([fin, inf]), _OneRankComm(),
        sample=None).edges[0]
    # sanity: single-rank distributed fit == plain fit on the same data
    want = QuantileBinner(8).fit(np.concatenate([fin, inf]),
                                 sample=None).edges[0]
    np.testing.assert_array_equal(np.isinf(edges), np.isinf(want))
    # two-rank merge: half the total mass is inf, so the top edges
    # (quantiles > 1/2) must be inf, and the bottom ones finite
    sk = [b.local_sketch(s, sample=None) for s in (fin, inf)]
    b2 = QuantileBinner(8)
    b2.merge_sketches(np.stack([s.values for s in sk]),
                      np.stack([s.counts for s in sk]),
                      np.asarray([[1.0], [0.0]], np.float32))
    assert np.isinf(b2.edges[0][-2:]).all(), b2.edges
    assert np.isfinite(b2.edges[0][:3]).all(), b2.edges


def test_merge_sketch_edge_count_mismatch_raises():
    b = QuantileBinner(8)            # needs Q+1 = 9 points per feature
    with pytest.raises(Mp4jError):
        b.merge_sketches(np.zeros((2, 1, 3), np.float32),
                         np.ones((2, 1), np.float32))


def test_fit_distributed_over_socket_backend(rng):
    """fit_distributed on the real socket backend: every rank ends with
    identical edges matching the host-side merge of the same shards."""
    from helpers import run_slaves

    N, F, B, R = 8_000, 3, 16, 4
    X = rng.standard_normal((N, F)).astype(np.float32)
    shards = np.array_split(X, R)

    def job(slave, rank):
        binner = QuantileBinner(B).fit_distributed(
            shards[rank], slave, sample=None)
        return binner.edges

    results = run_slaves(R, job)
    for e in results[1:]:
        np.testing.assert_array_equal(e, results[0])
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    np.testing.assert_allclose(results[0], b.edges, rtol=1e-6, atol=1e-6)


def test_local_sketch_weight_is_full_shard_count(rng):
    """Merge weights must reflect the FULL shard size even when the
    sketch itself is computed on a row sample — otherwise a large
    sampled shard weighs the same as a small unsampled one."""
    X_big = rng.standard_normal((10_000, 2)).astype(np.float32) + 5.0
    X_small = rng.standard_normal((1_000, 2)).astype(np.float32) - 5.0
    b = QuantileBinner(8)
    sk_big, c_big, *_ = b.local_sketch(X_big, sample=500, seed=0)
    sk_small, c_small, *_ = b.local_sketch(X_small, sample=500, seed=0)
    np.testing.assert_array_equal(c_big, [10_000, 10_000])
    np.testing.assert_array_equal(c_small, [1_000, 1_000])
    b.merge_sketches(np.stack([sk_big, sk_small]),
                     np.stack([c_big, c_small]))
    # 10:1 mass -> the median edge must sit in the big shard's mode
    mid = b.edges[0][len(b.edges[0]) // 2]
    assert mid > 3.0, mid


def test_local_sketch_inf_sentinels(rng):
    """inf sentinels are data (as in fit): the sketch stays monotone
    and a single-rank merge keeps the inf top edges."""
    col = np.concatenate([rng.standard_normal(1000).astype(np.float32),
                          np.full(300, np.inf, np.float32)])
    X = col[:, None]
    b = QuantileBinner(8)
    sk, c, fin, _ = b.local_sketch(X, sample=None)
    assert c[0] == 1300 and fin[0] == 1.0
    assert not np.isnan(sk).any()
    assert (sk[0][1:] >= sk[0][:-1]).all(), sk   # inf-safe monotonicity
    b.merge_sketches(sk[None], c[None])
    want = QuantileBinner(8).fit(X, sample=None).edges[0]
    # both must agree on which edges are inf, and on the finite ones
    np.testing.assert_array_equal(np.isinf(b.edges[0]), np.isinf(want))
    f = np.isfinite(want)
    np.testing.assert_allclose(b.edges[0][f], want[f], rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------- sketch-merge property tests
@st.composite
def _shard_sets(draw):
    """Random shard lists: 1-5 shards, 1-3 features, varied sizes and
    scales, optional NaN contamination."""
    R = draw(st.integers(1, 5))
    F = draw(st.integers(1, 3))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    shards = []
    for _ in range(R):
        n = draw(st.integers(5, 400))
        s = (rng.standard_normal((n, F)) *
             draw(st.floats(0.1, 100.0)) +
             draw(st.floats(-50.0, 50.0))).astype(np.float32)
        if draw(st.booleans()):
            s[rng.random((n, F)) < 0.2] = np.nan
        shards.append(s)
    # every feature must have data somewhere
    data = np.concatenate(shards)
    for f in range(F):
        if np.isnan(data[:, f]).all():
            shards[0][:, f] = rng.standard_normal(len(shards[0]))
    return shards


@settings(max_examples=30, deadline=None)
@given(_shard_sets(), st.integers(3, 32))
def test_merge_edges_monotone_and_bounded(shards, B):
    """Merged edges are nondecreasing per feature and lie within the
    pooled data's [min, max]."""
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    data = np.concatenate(shards)
    for f in range(b.edges.shape[0]):
        e = b.edges[f]
        assert (e[1:] >= e[:-1]).all()
        col = data[:, f]
        col = col[~np.isnan(col)]
        assert e[0] >= col.min() - 1e-4
        assert e[-1] <= col.max() + 1e-4


@settings(max_examples=30, deadline=None)
@given(_shard_sets(), st.integers(3, 16), st.integers(0, 2**31 - 1))
def test_merge_is_shard_order_invariant(shards, B, seed):
    """Rank order must not affect the merged edges (the distributed fit
    must give every rank the same answer regardless of rank ids)."""
    b1, b2 = QuantileBinner(B), QuantileBinner(B)
    sk = [b1.local_sketch(s, sample=None) for s in shards]
    edges = np.stack([s.values for s in sk])
    counts = np.stack([s.counts for s in sk])
    perm = np.random.default_rng(seed).permutation(len(shards))
    b1.merge_sketches(edges, counts)
    b2.merge_sketches(edges[perm], counts[perm])
    np.testing.assert_allclose(b1.edges, b2.edges, rtol=1e-6, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(_shard_sets(), st.integers(3, 16))
def test_single_concatenated_shard_matches_fit(shards, B):
    """A one-shard merge must reproduce fit() on the same data — exact
    for DISTINCT-VALUED data (the _shard_sets strategy draws tie-free
    float32 normals; ties collapse sketch points into CDF jumps whose
    inversion legitimately differs from nanquantile's order-statistic
    interpolation — see test_merge_with_tied_values for what IS
    guaranteed under ties)."""
    data = np.concatenate(shards)
    b = QuantileBinner(B)
    sk, c, *_ = b.local_sketch(data, sample=None)
    b.merge_sketches(sk[None], c[None])
    want = QuantileBinner(B).fit(data, sample=None)
    np.testing.assert_allclose(b.edges, want.edges, rtol=1e-5, atol=1e-5)


def _tie_aware_position_err(col, edges, qs):
    """Distance from each target quantile q to the pooled empirical CDF
    INTERVAL [F(edge-), F(edge)] at the edge — the natural sketch-error
    metric under ties, where a point-position metric would charge an
    edge sitting (correctly) inside a CDF jump for the whole jump."""
    col = np.sort(col[~np.isnan(col)])
    M = col.size
    L = np.searchsorted(col, edges, side="left") / M
    R = np.searchsorted(col, edges, side="right") / M
    return np.maximum(0.0, np.maximum(L - qs, qs - R))


def test_tie_mass_rides_the_merge(rng):
    """90% of the mass in ONE tied value: every internal quantile sits
    strictly inside the jump, so all merged edges must equal the tied
    value exactly — matching fit() — instead of smearing toward the
    tail (the pre-round-4 grid-CDF merge smeared)."""
    B, R, N = 8, 3, 9_000
    col = np.where(rng.random(N) < 0.9, 0.0,
                   rng.uniform(1.0, 2.0, N)).astype(np.float32)
    want = QuantileBinner(B).fit(col[:, None], sample=None).edges[0]
    np.testing.assert_array_equal(want, np.zeros(B - 1))  # all qs < .9
    shards = [col[i::R][:, None] for i in range(R)]
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]),
                     np.stack([s.finite for s in sk]),
                     np.stack([s.cdf for s in sk]))
    np.testing.assert_array_equal(b.edges[0], want)


@st.composite
def _tied_shard_sets(draw):
    """Tie-heavy shards: ~90% of rows land on 5 distinct support
    values, the rest are continuous noise."""
    R = draw(st.integers(1, 4))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    support = np.sort(rng.standard_normal(5) * 3).astype(np.float32)
    shards = []
    for _ in range(R):
        n = draw(st.integers(60, 400))
        tied = support[rng.integers(0, 5, n)]
        cont = rng.standard_normal(n).astype(np.float32)
        shards.append(np.where(rng.random(n) < 0.9, tied,
                               cont)[:, None].astype(np.float32))
    return shards


@settings(max_examples=25, deadline=None)
@given(_tied_shard_sets(), st.integers(4, 16))
def test_heavy_ties_position_bound(shards, B):
    """Under 90%-mass-in-5-values
    the merged edges must land within 2/Q of the target quantiles in
    POOLED-CDF position (tie-aware: a q inside a jump an edge sits on
    costs 0) — the same documented bound as the continuous case, which
    the pre-round-4 merge could not meet under ties."""
    data = np.concatenate(shards)[:, 0]
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]),
                     np.stack([s.finite for s in sk]),
                     np.stack([s.cdf for s in sk]))
    qs = np.arange(1, B) / B
    err = _tie_aware_position_err(data, b.edges[0], qs)
    assert err.max() < 2.0 / B, (err, b.edges)
    # the single-host fit clears the same bar (sanity for the metric)
    exact = QuantileBinner(B).fit(data[:, None], sample=None)
    err_fit = _tie_aware_position_err(data, exact.edges[0], qs)
    assert err_fit.max() < 2.0 / B, err_fit


def test_merge_with_tied_values(rng):
    """Heavily tied data (integer-coded / clipped features) collapses
    sketch points into CDF jumps; like any quantile-of-quantiles
    sketch, the merge is then NOT exact against fit() — but it must
    stay well-formed: monotone edges inside [min, max], every edge a
    plausible value, and transform output in range."""
    B, R = 8, 3
    col = rng.integers(0, 5, 9_000).astype(np.float32)   # 5 distinct
    shards = [col[i::R][:, None] for i in range(R)]
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    e = b.edges[0]
    assert (e[1:] >= e[:-1]).all()
    assert e[0] >= 0.0 and e[-1] <= 4.0
    out = b.transform(col[:, None])
    assert out.min() >= 0 and out.max() < B
    # a constant feature is the degenerate extreme: single-bin output
    const = np.full((600, 1), 7.0, np.float32)
    bc = QuantileBinner(B)
    skc, cc, *_ = bc.local_sketch(const, sample=None)
    bc.merge_sketches(skc[None], cc[None])
    assert len(np.unique(bc.transform(const))) == 1


def test_fit_distributed_over_thread_backend(rng):
    """fit_distributed on the thread backend: the comm duck-type (rank /
    slave_num / allgather_array) spans all three SPMD backends."""
    from ytk_mp4j_tpu.comm.thread_comm import ThreadCommSlave

    from test_thread_comm import run_threads

    N, F, B, R = 6_000, 3, 16, 4
    X = rng.standard_normal((N, F)).astype(np.float32)
    shards = np.array_split(X, R)
    slaves = ThreadCommSlave.spawn_group(R)
    results = run_threads(
        slaves,
        lambda sl, r: QuantileBinner(B).fit_distributed(
            shards[r], sl, sample=None).edges)
    for e in results[1:]:
        np.testing.assert_array_equal(e, results[0])
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None) for s in shards]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]))
    np.testing.assert_allclose(results[0], b.edges, rtol=1e-6, atol=1e-6)


def test_fit_distributed_config_mismatch_raises(rng):
    """Ranks disagreeing on n_bins must fail loudly, not merge
    garbage: the size pre-exchange catches it before the sketch
    allgather can shear."""
    from helpers import run_slaves

    X = rng.standard_normal((400, 2)).astype(np.float32)
    shards = np.array_split(X, 2)

    def job(slave, rank):
        B = 8 if rank == 0 else 16
        QuantileBinner(B).fit_distributed(shards[rank], slave,
                                          sample=None)

    with pytest.raises(Mp4jError, match="mismatch"):
        run_slaves(2, job)


# ------------------------------------------------- weighted sketches
def test_fit_weighted_matches_numpy_oracle(rng):
    """Weighted fit == numpy's weighted quantiles (inverted_cdf is the
    one method numpy defines weights for; same convention here)."""
    N, F, B = 5_000, 3, 16
    X = np.stack([rng.standard_normal(N),
                  rng.lognormal(0.0, 1.0, N),
                  rng.integers(0, 7, N).astype(np.float64)],
                 axis=1).astype(np.float32)
    w = rng.gamma(0.3, 2.0, N)       # heavily skewed weights
    b = QuantileBinner(B).fit(X, sample=None, sample_weight=w)
    qs = np.arange(1, B) / B
    for f in range(F):
        want = np.quantile(X[:, f].astype(np.float64), qs,
                           method="inverted_cdf", weights=w)
        np.testing.assert_allclose(b.edges[f], want, rtol=1e-6,
                                   atol=1e-6)


def test_fit_weighted_integer_weights_equal_duplication(rng):
    """Integer weights must bin exactly like physically duplicated
    rows (the defining property of weighted quantiles), including
    heavy ties."""
    N, B = 800, 8
    X = rng.integers(0, 5, (N, 2)).astype(np.float32)   # many ties
    k = rng.integers(1, 6, N)
    b_w = QuantileBinner(B).fit(X, sample=None,
                                sample_weight=k.astype(np.float64))
    b_d = QuantileBinner(B).fit(np.repeat(X, k, axis=0), sample=None,
                                sample_weight=np.ones(int(k.sum())))
    np.testing.assert_array_equal(b_w.edges, b_d.edges)


def test_weighted_sketch_single_rank_merge_matches_weighted_fit(rng):
    """A one-shard weighted merge reproduces the weighted fit exactly
    for distinct-valued data (the ordinates land on the grid, so the
    inversion hits every quantile point)."""
    N, B = 4_000, 16
    X = rng.standard_normal((N, 2)).astype(np.float32)
    w = rng.gamma(1.0, 1.0, N)
    b = QuantileBinner(B)
    sk = b.local_sketch(X, sample=None, sample_weight=w)
    b.merge_sketches(sk.values[None], sk.counts[None],
                     sk.finite[None], cdf_stack=sk.cdf[None])
    want = QuantileBinner(B).fit(X, sample=None, sample_weight=w)
    np.testing.assert_allclose(b.edges, want.edges, rtol=1e-5,
                               atol=1e-5)


def test_weighted_sketch_merge_skewed_shards(rng):
    """Pooled weighted merge across shards with SKEWED weights: edges
    must land within the documented 2/Q of the pooled weighted
    quantile positions; a tied value holding ~90% of the total WEIGHT
    (not rows) must capture every internal edge exactly."""
    B, R = 16, 3
    qs = np.arange(1, B) / B
    # continuous case, weights concentrated on one shard
    shards = [rng.standard_normal((3_000, 1)).astype(np.float32) + r
              for r in range(R)]
    weights = [np.full(3_000, 10.0 ** r) for r in range(R)]
    b = QuantileBinner(B)
    sk = [b.local_sketch(s, sample=None, sample_weight=w)
          for s, w in zip(shards, weights)]
    b.merge_sketches(np.stack([s.values for s in sk]),
                     np.stack([s.counts for s in sk]),
                     np.stack([s.finite for s in sk]),
                     cdf_stack=np.stack([s.cdf for s in sk]))
    pooled = np.concatenate(shards)[:, 0].astype(np.float64)
    pw = np.concatenate(weights)
    want = np.quantile(pooled, qs, method="inverted_cdf", weights=pw)
    # position error in WEIGHTED quantile space
    o = np.argsort(pooled)
    cw = np.cumsum(pw[o]) / pw.sum()
    for e, q in zip(b.edges[0], qs):
        lo = np.searchsorted(pooled[o], e, side="left")
        hi = np.searchsorted(pooled[o], e, side="right")
        fl = cw[lo - 1] if lo > 0 else 0.0
        fr = cw[hi - 1] if hi > 0 else 0.0
        err = max(0.0, max(fl - q, q - fr))
        assert err < 2.0 / B, (e, q, err, want)
    # heavy-tie-by-weight case: one row value owns 99% of the total
    # weight, so every internal quantile of a B=16 binner lands
    # strictly inside its CDF jump
    vals = rng.standard_normal((1_000, 1)).astype(np.float32)
    vals[0, 0] = 0.5
    w = np.ones(1_000)
    w[0] = 99_000.0
    halves = [(vals[:500], w[:500]), (vals[500:], w[500:])]
    b2 = QuantileBinner(B)
    sk2 = [b2.local_sketch(s, sample=None, sample_weight=ww)
           for s, ww in halves]
    b2.merge_sketches(np.stack([s.values for s in sk2]),
                      np.stack([s.counts for s in sk2]),
                      np.stack([s.finite for s in sk2]),
                      cdf_stack=np.stack([s.cdf for s in sk2]))
    # every internal quantile (1/B..15/16) falls inside the 90% jump
    assert (b2.edges[0] == np.float32(0.5)).all(), b2.edges[0]


def test_weighted_fit_distributed_matches_weighted_fit(rng):
    """fit_distributed with per-rank weights pools to the weighted fit
    (single-rank comm: exact; the multi-rank path shares the same
    merge, covered by the skewed-shard test above)."""
    X = rng.standard_normal((2_000, 2)).astype(np.float32)
    w = rng.gamma(1.0, 1.0, 2_000)
    b = QuantileBinner(8).fit_distributed(X, _OneRankComm(),
                                          sample=None, sample_weight=w)
    want = QuantileBinner(8).fit(X, sample=None, sample_weight=w)
    np.testing.assert_allclose(b.edges, want.edges, rtol=1e-5,
                               atol=1e-5)


def test_weighted_fit_distributed_multirank_socket(rng):
    """Weighted fit_distributed over REAL socket slaves: per-rank
    weighted shards pool to job-identical edges within the pooled
    weighted-quantile tolerance."""
    from helpers import run_slaves

    B, R = 8, 3
    X = rng.standard_normal((3_000, 2)).astype(np.float32)
    w = rng.gamma(0.7, 1.0, 3_000)
    cuts = [0, 600, 1_800, 3_000]

    def job(slave, rank):
        s = slice(cuts[rank], cuts[rank + 1])
        return QuantileBinner(B).fit_distributed(
            X[s], slave, sample=None, sample_weight=w[s]).edges

    results = run_slaves(R, job)
    for e in results[1:]:
        np.testing.assert_array_equal(e, results[0])
    qs = np.arange(1, B) / B
    pooled = X[:, 0].astype(np.float64)
    o = np.argsort(pooled)
    cw = np.cumsum(w[o]) / w.sum()
    for e, q in zip(results[0][0], qs):
        lo = np.searchsorted(pooled[o], e, side="left")
        hi = np.searchsorted(pooled[o], e, side="right")
        fl = cw[lo - 1] if lo > 0 else 0.0
        fr = cw[hi - 1] if hi > 0 else 0.0
        assert max(0.0, max(fl - q, q - fr)) < 2.0 / B


def test_weight_validation_errors(rng):
    X = rng.standard_normal((10, 2)).astype(np.float32)
    b = QuantileBinner(4)
    with pytest.raises(Mp4jError, match="sample_weight"):
        b.fit(X, sample_weight=np.ones(5))
    with pytest.raises(Mp4jError, match="finite and non-negative"):
        b.fit(X, sample_weight=-np.ones(10))
    with pytest.raises(Mp4jError, match="finite and non-negative"):
        b.fit(X, sample_weight=np.full(10, np.nan))
    # zero-weight rows carry no evidence: a feature whose only finite
    # values have weight 0 must raise like an all-NaN feature
    X2 = np.stack([np.arange(10, dtype=np.float32),
                   np.full(10, np.nan, np.float32)], axis=1)
    X2[:3, 1] = 1.0
    w = np.ones(10)
    w[:3] = 0.0
    with pytest.raises(Mp4jError, match="no\nfinite values|no finite"):
        b.fit(X2, sample_weight=w)


# ``#edges <= x`` by search (ISSUE 48): ``_count_edges`` off the TPU and
# the ``mp4j_bin`` kernel, interpreted, against the plain count.
def _edge_cases(rng, F, E, N, order):
    """A table [N, F] and edges [F, E]: duplicate edges, +-inf edges,
    cells equal to an edge, +-inf cells, NaN cells and one column of
    nothing else; the edges sorted, reversed, or +inf first."""
    edges = np.sort(np.round(rng.standard_normal((F, E)), 1), axis=1)
    if E >= 4:
        edges[0, -1] = np.inf
        edges[0, 0] = -np.inf
        edges[1 % F, -2:] = np.inf
    X = (3 * rng.standard_normal((N, F))).astype(np.float32)
    X[rng.random((N, F)) < 0.3] = np.nan
    ties = rng.integers(0, E, (N // 4, F))
    X[:N // 4] = np.take_along_axis(edges, ties.T, axis=1).T
    X[N // 4] = np.inf
    X[N // 4 + 1] = -np.inf
    X[:, F - 1] = np.nan
    if order == "reversed":
        edges = edges[:, ::-1]
    elif order == "inf_first":              # what ``_edges_of`` can give
        edges = np.roll(edges, 1, axis=1)
    return X, np.ascontiguousarray(edges, np.float32)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
@pytest.mark.parametrize("shift", [False, True])
@pytest.mark.parametrize("F,E,N,order,block_rows", [
    (8, 1, 130, "sorted", None), (8, 127, 300, "reversed", None),
    (8, 128, 300, "inf_first", None), (8, 254, 1100, "sorted", 1024),
    (16, 254, 300, "inf_first", None), (16, 255, 130, "reversed", None),
    (8, 300, 300, "inf_first", None), (24, 254, 2100, "reversed", 1024),
    (28, 254, 1100, "inf_first", None), (28, 300, 130, "sorted", None),
    (12, 255, 8300, "reversed", 1024), (40, 127, 130, "inf_first", None)])
def test_count_edges_is_the_plain_count(rng, monkeypatch, form, shift, F, E,
                                        N, order, block_rows):
    """Every cell's bin is ``(x >= edges).sum()``, NaN 0 and the others
    one more under ``shift``: widths that rest in sublane tiles (8, 16,
    24, 40: one to five blocks of eight columns) and that do not (12,
    28: a block a column), one and several registers of edges, a table
    under a block, and a ragged last block of rows (blocks of 1,024
    rows: 1,100 and 2,100 rows in sublane tiles, 8,300 rows of a column
    in blocks of 8,192)."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import binning
    from ytk_mp4j_tpu.ops import bin_kernel

    X, edges = _edge_cases(rng, F, E, N, order)
    want = (X[..., None] >= edges).sum(-1)
    if shift:
        want = np.where(np.isnan(X), 0, want + 1)
    if form == "kernel":
        if block_rows:
            monkeypatch.setattr(bin_kernel, "_BLOCK_ROWS", block_rows)
        got = bin_kernel.pallas_bin_counts(jnp.asarray(X), jnp.asarray(edges),
                                           shift, interpret=True)
    else:
        got = binning._count_edges(jnp.asarray(X), jnp.asarray(edges), shift)
    assert got.dtype == jnp.int32 and got.shape == X.shape
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n_edges,steps,registers", [
    (1, 1, 1), (2, 2, 1), (127, 7, 1), (128, 8, 2), (254, 8, 2),
    (255, 8, 2), (256, 9, 4), (510, 9, 4), (65534, 16, 512)])
def test_search_table_is_the_edges_in_level_order(rng, n_edges, steps,
                                                  registers):
    """Node i's children are 2i and 2i + 1, an in-order walk of the
    nodes gives the sorted edges and then NaN, and the table is whole
    registers of 128 lanes."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.ops import bin_kernel

    edges = rng.standard_normal((2, n_edges)).astype(np.float32)
    table = np.asarray(bin_kernel.search_table(jnp.asarray(edges)))
    assert bin_kernel.search_steps(n_edges) == steps
    assert table.shape == (2, 128 * registers)

    def in_order(i):
        return (in_order(2 * i) + [i] + in_order(2 * i + 1)
                if i < 2 ** steps else [])

    walked = table[:, in_order(1)]
    np.testing.assert_array_equal(walked[:, :n_edges], np.sort(edges, axis=1))
    assert np.isnan(walked[:, n_edges:]).all() and np.isnan(table[:, 0]).all()


@pytest.mark.parametrize("n_edges,block", [
    (1, (8, 4096)), (254, (8, 4096)), (255, (8, 4096)),
    (510, (8, 3072)), (998, (8, 2048)), (4094, (8, 1024)),
    (65534, (8, 1024))])
def test_a_block_shortens_with_the_registers_its_edges_take(n_edges, block):
    from ytk_mp4j_tpu.ops import bin_kernel

    assert bin_kernel.bin_blocks(n_edges) == block
