"""GBDT north-star workload: distributed histogram build + allreduce +
tree training over the virtual mesh, checked against a numpy oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from ytk_mp4j_tpu.models.gbdt import (
    GBDTConfig, GBDTTrainer, best_splits, build_histograms, predict_tree,
    train_tree_shard,
)
from ytk_mp4j_tpu.parallel import make_mesh, make_hier_mesh


def np_histograms(bins, g, h, node_ids, n_nodes, F, B):
    hg = np.zeros((n_nodes, F, B), np.float32)
    hh = np.zeros((n_nodes, F, B), np.float32)
    for i in range(bins.shape[0]):
        for f in range(F):
            hg[node_ids[i], f, bins[i, f]] += g[i]
            hh[node_ids[i], f, bins[i, f]] += h[i]
    return hg, hh


def test_histograms_match_numpy(rng):
    N, F, B = 200, 5, 8
    cfg = GBDTConfig(n_features=F, n_bins=B)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = np.ones(N, np.float32)
    node_ids = rng.integers(0, 4, N).astype(np.int32)
    hg, hh = build_histograms(jnp.array(bins), jnp.array(g), jnp.array(h),
                              jnp.array(node_ids), 4, cfg)
    want_g, want_h = np_histograms(bins, g, h, node_ids, 4, F, B)
    np.testing.assert_allclose(np.asarray(hg), want_g, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hh), want_h, rtol=1e-4, atol=1e-4)


def test_hist_strategies_agree(rng):
    """The pair-packed scatter and one-hot matmul strategies (see the
    TPU performance note in models/gbdt.py) must match flat and numpy —
    N=1500 > _MATMUL_TILE also exercises the matmul path's
    non-tile-multiple padding (T=2 tiles, 548 pad rows)."""
    N, F, B = 1500, 6, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = np.ones(N, np.float32)
    node_ids = rng.integers(0, 4, N).astype(np.int32)
    outs = {}
    for mode in ("pallas", "matmul", "pair", "flat"):
        cfg = GBDTConfig(n_features=F, n_bins=B, hist_mode=mode)
        outs[mode] = build_histograms(
            jnp.array(bins), jnp.array(g), jnp.array(h),
            jnp.array(node_ids), 4, cfg)
    want_g, want_h = np_histograms(bins, g, h, node_ids, 4, F, B)
    for mode in ("pallas", "matmul", "pair", "flat"):
        np.testing.assert_allclose(np.asarray(outs[mode][0]), want_g,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(outs[mode][1]), want_h,
                                   rtol=1e-4, atol=1e-4)


def test_logistic_objective_fits_and_matches_distributed(rng):
    """Binary-classification GBDT (the reference's Higgs objective):
    logloss falls below the base rate and the distributed run matches
    single-device."""
    N, F, B = 2048, 5, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 1] > B // 2).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.3,
                     n_trees=5, loss="logistic")

    dist = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, margins = dist.train(bins, y)
    p = dist.predict(bins, trees, proba=True)
    eps = 1e-7
    logloss = -np.mean(y * np.log(p + eps) + (1 - y) * np.log(1 - p + eps))
    base = y.mean()
    base_ll = -(base * np.log(base) + (1 - base) * np.log(1 - base))
    assert logloss < base_ll * 0.5
    assert ((p > 0.5) == (y > 0.5)).mean() > 0.95

    single = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees_s, margins_s = single.train(bins, y)
    np.testing.assert_allclose(margins[:N], margins_s[:N], rtol=1e-4,
                               atol=1e-5)


def test_bad_loss_rejected():
    from ytk_mp4j_tpu.exceptions import Mp4jError
    with pytest.raises(Mp4jError):
        GBDTConfig(loss="hinge")
    with pytest.raises(Mp4jError):
        GBDTConfig(loss="softmax", n_classes=1)


def test_eval_set_and_early_stopping(rng):
    """Validation metric falls while signal is being learned; on pure
    noise, early stopping truncates the ensemble to the best round."""
    N, F, B = 2048, 4, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 0] / B + 0.05 * rng.standard_normal(N)).astype(np.float32)
    va_bins = rng.integers(0, B, (512, F)).astype(np.int32)
    va_y = (va_bins[:, 0] / B
            + 0.05 * rng.standard_normal(512)).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=8,
                     learning_rate=0.4)
    tr = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, _ = tr.train(bins, y, eval_set=(va_bins, va_y))
    hist = tr.eval_history_
    assert len(hist) == 8
    assert hist[-1] < hist[0] * 0.5          # metric improves on signal
    # incremental margins == full re-predict
    np.testing.assert_allclose(
        tr._eval_metric(tr.predict(va_bins, trees), va_y), hist[-1],
        rtol=1e-5)

    # pure-noise labels: stops early, truncates to the best round, and
    # the returned margins match the truncated ensemble
    y_noise = rng.standard_normal(N).astype(np.float32)
    va_noise = rng.standard_normal(512).astype(np.float32)
    tr2 = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees2, margins2 = tr2.train(bins, y_noise,
                                 eval_set=(va_bins, va_noise),
                                 early_stopping_rounds=2)
    assert len(trees2) < 8
    best = int(np.argmin(tr2.eval_history_))
    assert len(trees2) == best + 1
    np.testing.assert_allclose(margins2[:N], tr2.predict(bins, trees2),
                               rtol=1e-5, atol=1e-6)

    from ytk_mp4j_tpu.exceptions import Mp4jError
    with pytest.raises(Mp4jError):
        tr2.train(bins, y, early_stopping_rounds=3)   # no eval_set


def test_sample_weight_and_importance(rng):
    """Instance weights steer training (a heavily-weighted subset
    dominates); feature importance concentrates on the signal feature."""
    N, F, B = 2048, 4, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    # two conflicting signals: feature 0 for the first half, feature 1
    # for the second; weights make the second half dominate
    y = np.where(np.arange(N) < N // 2,
                 (bins[:, 0] / B), (bins[:, 1] / B)).astype(np.float32)
    w = np.where(np.arange(N) < N // 2, 1e-3, 1.0).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=4,
                     learning_rate=0.3)
    tr = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, _ = tr.train(bins, y, sample_weight=w)
    imp = tr.feature_importance(trees)
    assert imp.shape == (F,)
    assert abs(imp.sum() - 1.0) < 1e-9
    assert imp[1] > imp[0], imp       # weighted half's feature dominates

    # phantom splits from empty/pure nodes must not count: with signal
    # only on feature 3 and a deep tree, no importance leaks to feat 0
    bins2 = rng.integers(0, 4, (8, F)).astype(np.int32)
    y2 = (bins2[:, 3] > 1).astype(np.float32)
    cfg2 = GBDTConfig(n_features=F, n_bins=4, depth=5, n_trees=1)
    tr2 = GBDTTrainer(cfg2, mesh=make_mesh(1))
    trees2, _ = tr2.train(bins2, y2)
    imp2 = tr2.feature_importance(trees2)
    assert imp2[3] == 1.0, imp2

    from ytk_mp4j_tpu.exceptions import Mp4jError
    with pytest.raises(Mp4jError):
        tr.train(bins, y, sample_weight=np.ones(N - 1, np.float32))


def test_split_regularization_thresholds(rng):
    """min_split_gain freezes below-threshold nodes (all samples route
    left); min_child_hessian disqualifies tiny-child splits; both still
    train and an absurd min_split_gain yields single-leaf trees."""
    N, F, B = 1024, 4, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 0] / B + 0.05 * rng.standard_normal(N)).astype(np.float32)

    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=3,
                     learning_rate=0.3, min_split_gain=1e9)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, preds = tr.train(bins, y)
    # every node frozen -> all samples share one leaf -> the tree output
    # is constant = learning_rate * global mean correction
    for t in trees:
        bins_arr = np.asarray(t[1])
        assert (bins_arr == B - 1).all()

    cfg2 = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=4,
                      learning_rate=0.3, min_split_gain=1e-4,
                      min_child_hessian=2.0)
    tr2 = GBDTTrainer(cfg2, mesh=make_mesh(2))
    _, preds2 = tr2.train(bins, y)
    mse = float(np.mean((preds2[:N] - y) ** 2))
    assert mse < float(np.var(y)) * 0.5

    # min_child_hessian ALONE (min_split_gain=0): a node where every
    # candidate is disqualified must freeze, not split at feat 0/bin 0
    cfg3 = GBDTConfig(n_features=F, n_bins=B, depth=6, n_trees=1,
                      learning_rate=0.3, min_child_hessian=float(N))
    trees3, _ = GBDTTrainer(cfg3, mesh=make_mesh(1)).train(bins, y)
    # no split can satisfy both children >= N hessian -> all frozen
    assert (np.asarray(trees3[0][1]) == B - 1).all()


def test_stochastic_boosting(rng):
    """subsample/colsample < 1: training still fits, is deterministic
    under a fixed seed, and varies with the seed."""
    N, F, B = 2048, 6, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 0] / B + 0.05 * rng.standard_normal(N)).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.3,
                     n_trees=6, subsample=0.7, colsample=0.7)
    tr = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees_a, preds_a = tr.train(bins, y, seed=0)
    mse = float(np.mean((preds_a[:N] - y) ** 2))
    assert mse < float(np.var(y)) * 0.5

    tr2 = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees_b, preds_b = tr2.train(bins, y, seed=0)
    np.testing.assert_array_equal(preds_a, preds_b)   # same seed

    trees_c, preds_c = tr.train(bins, y, seed=1)
    assert not np.array_equal(preds_a, preds_c)       # different seed

    from ytk_mp4j_tpu.exceptions import Mp4jError
    with pytest.raises(Mp4jError):
        GBDTConfig(subsample=0.0)
    with pytest.raises(Mp4jError):
        GBDTConfig(colsample=1.5)


def test_colsample_masks_features(rng):
    """With only one feature allowed to win, every split must use it
    (verified by comparing against a run whose data makes the masked
    features strictly better)."""
    N, F, B = 1024, 4, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    # feature 3 is perfectly predictive; others noise
    y = (bins[:, 3] > B // 2).astype(np.float32)
    # colsample so small the fallback keeps exactly one feature; over
    # several seeds, some tree must be forced off feature 3 yet still
    # split on SOME feature in range
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, n_trees=3,
                     subsample=1.0, colsample=0.26)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees, _ = tr.train(bins, y, seed=42)
    feats = np.concatenate([np.asarray(t[0]) for t in trees])
    assert ((feats >= 0) & (feats < F)).all()
    # not every split can be feature 3 under aggressive masking
    assert (feats != 3).any()


def test_softmax_out_of_range_labels_rejected(rng):
    cfg = GBDTConfig(n_features=2, n_bins=4, depth=2, n_trees=1,
                     loss="softmax", n_classes=3)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    bins = rng.integers(0, 4, (32, 2)).astype(np.int32)
    from ytk_mp4j_tpu.exceptions import Mp4jError
    with pytest.raises(Mp4jError):
        tr.train(bins, np.full(32, 3, np.int32))     # == n_classes
    with pytest.raises(Mp4jError):
        tr.train(bins, np.full(32, -1, np.int32))


def test_softmax_multiclass_fits_and_roundtrips(rng, tmp_path):
    """Multiclass GBDT: one tree per class per round; accuracy beats
    the base rate; distributed matches single-device; save/load/predict
    round-trips."""
    N, F, B, C = 1500, 4, 16, 3
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = np.clip(bins[:, 2] * C // B, 0, C - 1).astype(np.int32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.4,
                     n_trees=4, loss="softmax", n_classes=C)

    dist = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, margins = dist.train(bins, y)
    assert margins.shape == (dist.n_shards * ((N + 3) // 4), C)
    proba = dist.predict(bins, trees, proba=True)
    assert proba.shape == (N, C)
    np.testing.assert_allclose(proba.sum(1), 1.0, rtol=1e-5)
    acc = float((proba.argmax(1) == y).mean())
    assert acc > 0.9

    single = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees_s, margins_s = single.train(bins, y)
    np.testing.assert_allclose(margins[:N], margins_s[:N], rtol=1e-4,
                               atol=1e-5)

    path = str(tmp_path / "mc.npz")
    dist.save_model(path, trees)
    cfg2, trees2, _ = GBDTTrainer.load_model(path)
    assert cfg2 == cfg
    serve = GBDTTrainer(cfg2, mesh=make_mesh(1))
    np.testing.assert_allclose(serve.predict(bins, trees2),
                               dist.predict(bins, trees), rtol=1e-6)


def test_empty_leaf_nan_stays_isolated(rng):
    """reg_lambda=0 + an empty leaf gives that leaf value -0/0 = NaN;
    the one-hot selects must confine it to rows that route there (none),
    exactly like the gathers they replaced — one poisoned table entry
    must not contaminate every sample's prediction."""
    N, F, B = 256, 3, 4
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=4, reg_lambda=0.0,
                     learning_rate=0.5)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = rng.standard_normal(N).astype(np.float32)
    preds = np.zeros(N, np.float32)
    new_preds, tree = train_tree_shard(
        jnp.array(bins), jnp.array(y), jnp.array(preds), cfg)
    # depth-4 over 256 samples: empty leaves are essentially guaranteed
    assert np.isnan(np.asarray(tree[3])).any(), "test needs an empty leaf"
    assert np.isfinite(np.asarray(new_preds)).all()
    applied = np.asarray(predict_tree(jnp.array(bins), tree, cfg))
    assert np.isfinite(applied).all()


def test_best_splits_prefers_separating_feature():
    # two nodes; feature 1 cleanly separates grads in node 0
    F, B = 3, 4
    hg = np.zeros((1, F, B), np.float32)
    hh = np.ones((1, F, B), np.float32)
    # feature 1: strong negative grads below bin 2, positive above
    hg[0, 1, 0] = -10.0
    hg[0, 1, 1] = -8.0
    hg[0, 1, 2] = 9.0
    hg[0, 1, 3] = 9.0
    feat, bin_, gain, dir_ = best_splits(jnp.array(hg), jnp.array(hh), 1.0)
    assert int(feat[0]) == 1
    assert int(bin_[0]) == 1
    assert float(gain[0]) > 0
    assert int(dir_[0]) == 0          # no missing handling: always left


def test_single_device_tree_reduces_loss(rng):
    N, F, B = 512, 6, 16
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.5)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    # target correlated with feature 0's bins
    y = (bins[:, 0] / B + 0.05 * rng.standard_normal(N)).astype(np.float32)
    preds = np.zeros(N, np.float32)
    new_preds, tree = train_tree_shard(
        jnp.array(bins), jnp.array(y), jnp.array(preds), cfg)
    mse0 = float(np.mean((preds - y) ** 2))
    mse1 = float(np.mean((np.asarray(new_preds) - y) ** 2))
    assert mse1 < mse0 * 0.5

    # predict_tree reproduces the training-time routing deltas
    delta = np.asarray(new_preds) - preds
    applied = cfg.learning_rate * np.asarray(
        predict_tree(jnp.array(bins), tree, cfg))
    np.testing.assert_allclose(applied, delta, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh_builder", [
    lambda: make_mesh(4),
    lambda: make_hier_mesh(2, 4),
], ids=["flat4", "hier2x4"])
def test_distributed_training_matches_single_device(mesh_builder, rng):
    """The histogram allreduce must make distributed training numerically
    equivalent to single-device training on the union of the data."""
    N, F, B = 1024, 4, 16
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.3,
                     n_trees=3)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (np.sin(bins[:, 1]) + 0.1 * rng.standard_normal(N)).astype(np.float32)

    dist = GBDTTrainer(cfg, mesh=mesh_builder())
    trees_d, preds_d = dist.train(bins, y)

    single = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees_s, preds_s = single.train(bins, y)

    np.testing.assert_allclose(preds_d[:N], preds_s[:N], rtol=1e-4,
                               atol=1e-5)
    for (f_d, b_d, d_d, v_d), (f_s, b_s, d_s, v_s) in zip(trees_d,
                                                          trees_s):
        np.testing.assert_array_equal(np.asarray(f_d), np.asarray(f_s))
        np.testing.assert_array_equal(np.asarray(b_d), np.asarray(b_s))
        np.testing.assert_array_equal(np.asarray(d_d), np.asarray(d_s))
        np.testing.assert_allclose(np.asarray(v_d), np.asarray(v_s),
                                   rtol=1e-4, atol=1e-5)


def test_training_fits_signal(rng):
    N, F, B = 2048, 5, 32
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=4, learning_rate=0.3,
                     n_trees=10)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = ((bins[:, 0] > B // 2).astype(np.float32)
         + 0.1 * rng.standard_normal(N).astype(np.float32))
    tr = GBDTTrainer(cfg, mesh=make_mesh(8))
    _, preds = tr.train(bins, y)
    mse = float(np.mean((preds[:N] - y) ** 2))
    assert mse < 0.05


def test_distributed_uneven_n_matches_single_device(rng):
    """Padding rows must be weight-0: N not divisible by shards has to
    reproduce single-device results exactly (review regression)."""
    N, F, B = 1001, 4, 16
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.3,
                     n_trees=2)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (np.cos(bins[:, 2]) + 0.1 * rng.standard_normal(N)).astype(np.float32)
    dist = GBDTTrainer(cfg, mesh=make_mesh(8))
    _, preds_d = dist.train(bins, y)
    single = GBDTTrainer(cfg, mesh=make_mesh(1))
    _, preds_s = single.train(bins, y)
    np.testing.assert_allclose(preds_d[:N], preds_s[:N], rtol=1e-4,
                               atol=1e-5)


def test_wrong_bins_width_rejected(rng):
    """A bin matrix whose width differs from cfg.n_features must raise,
    not silently route every sample left (one-hot feature select yields
    0 for out-of-range split features)."""
    from ytk_mp4j_tpu.exceptions import Mp4jError
    N, F, B = 256, 5, 8
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, n_trees=1)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = rng.standard_normal(N).astype(np.float32)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees, _ = tr.train(bins, y)
    narrow = bins[:, : F - 1]
    with pytest.raises(Mp4jError):
        tr.predict(narrow, trees)
    with pytest.raises(Mp4jError):
        tr.train(narrow, y)
    with pytest.raises(Mp4jError):
        tr.train(bins, y, eval_set=(narrow, y))


# ----------------------------------------------------------------------
# missing-value default direction + categorical splits (ytk-learn's
# data-handling features), checked against a compact numpy oracle
# ----------------------------------------------------------------------
def _oracle_tree(bins, g, h, cfg):
    """Depth-d level-wise numpy mirror of _build_tree with missing
    direction + categorical handling (exact f64 histograms)."""
    F, B, lam = cfg.n_features, cfg.n_bins, cfg.reg_lambda
    cats = set(cfg.categorical_features)
    N = bins.shape[0]
    node = np.zeros(N, np.int64)
    feats, bs, dirs = [], [], []
    for d in range(cfg.depth):
        n_nodes = 2 ** d
        bf, bb, bd, bg = (np.zeros(n_nodes, int), np.zeros(n_nodes, int),
                          np.zeros(n_nodes, int),
                          np.full(n_nodes, -np.inf))
        for n in range(n_nodes):
            m = node == n
            for f in range(F):
                hg = np.bincount(bins[m, f], weights=g[m], minlength=B)
                hh = np.bincount(bins[m, f], weights=h[m], minlength=B)
                Gt, Ht = hg.sum(), hh.sum()

                def score(G, H):
                    return G * G / (H + lam)

                for b in range(B - 1):      # B-1 excluded everywhere
                    if f in cats:
                        GL, HL = Gt - hg[b], Ht - hh[b]
                        variants = [(GL, HL, 0)]
                    else:
                        GL = hg[: b + 1].sum()
                        HL = hh[: b + 1].sum()
                        variants = [(GL, HL, 0)]
                        if cfg.missing_bin:
                            variants.append((GL - hg[0], HL - hh[0], 1))
                    for GL, HL, dr in variants:
                        gain = (score(GL, HL) + score(Gt - GL, Ht - HL)
                                - score(Gt, Ht))
                        if gain > bg[n]:
                            bf[n], bb[n], bd[n], bg[n] = f, b, dr, gain
            if not bg[n] > cfg.min_split_gain:
                bf[n], bb[n], bd[n] = 0, B - 1, 0
        feats.append(bf)
        bs.append(bb)
        dirs.append(bd)
        v = bins[np.arange(N), bf[node]]
        go_right = v > bb[node]
        if cfg.missing_bin:
            go_right = np.where(v == 0, bd[node] > 0, go_right)
        is_cat = np.isin(bf[node], list(cats)) if cats else np.zeros(N, bool)
        go_right = np.where(is_cat, (v == bb[node]) & (bb[node] != B - 1),
                            go_right)
        node = node * 2 + go_right
    leaves = 2 ** cfg.depth
    lg = np.bincount(node, weights=g, minlength=leaves)
    lh = np.bincount(node, weights=h, minlength=leaves)
    leaf = -lg / (lh + lam)
    return (np.concatenate(feats), np.concatenate(bs),
            np.concatenate(dirs), leaf)


def _train_one(bins, y, cfg):
    preds = np.zeros(len(y), np.float32)
    new_preds, tree = train_tree_shard(
        jnp.array(bins), jnp.array(y), jnp.array(preds), cfg)
    return np.asarray(new_preds), [np.asarray(t) for t in tree]


# (F, n_nodes) -> whether the level routes on sliced columns: both sides
# of ``route_sliced`` at rows of N lanes (F = 5, 28, 70: a slice reads
# its column) and at (8, 128) tiles (F = 40, 520: its tile row's eight)
_ROUTE_CASES = {
    (5, 1): True, (5, 4): True, (5, 32): False,
    (28, 1): True, (28, 4): True, (28, 16): True, (28, 32): False,
    (40, 1): True, (40, 4): True, (40, 32): False,
    (70, 1): True, (70, 4): True, (70, 32): True,
    (520, 1): True, (520, 4): True, (520, 32): True,
}


@pytest.mark.parametrize("rules", ["numeric", "missing+categorical"])
@pytest.mark.parametrize("F,n_nodes", list(_ROUTE_CASES))
def test_route_samples_matches_the_plain_statement(F, n_nodes, rules):
    """``bins[i, feat[node[i]]]`` by ``np.take_along_axis``, then the
    three rules, on either side of ``route_sliced``: a frozen node, two
    nodes on one feature, a node no sample is in, a categorical column
    and stored directions for the missing bucket."""
    from ytk_mp4j_tpu.models.gbdt import _route_samples, route_sliced

    assert route_sliced(n_nodes, F) == _ROUTE_CASES[F, n_nodes]
    rng = np.random.default_rng(100 * F + n_nodes)
    N, B = 333, 16
    full = rules == "missing+categorical"
    bins = rng.integers(0 if full else 1, B, (N, F)).astype(np.int32)
    node = rng.integers(0, n_nodes, N).astype(np.int32)
    feat = rng.integers(0, F, n_nodes).astype(np.int32)
    bin_ = rng.integers(0, B - 1, n_nodes).astype(np.int32)
    dir_ = rng.integers(0, 2, n_nodes).astype(np.int32)
    cat = np.zeros(F, bool)
    cat[F - 2] = True
    if n_nodes >= 4:
        feat[1] = feat[0]                   # two nodes on one feature
        node[node == 2] = 3                 # node 2 holds no sample
        feat[3], bin_[3] = F - 2, 5         # a categorical split
        bin_[n_nodes - 1], dir_[n_nodes - 1] = B - 1, 0     # frozen
    else:
        feat[0] = F - 2
    got = _route_samples(
        jnp.asarray(bins), jnp.asarray(node), jnp.asarray(feat),
        jnp.asarray(bin_), n_nodes, jnp.asarray(dir_),
        cat if full else None, full, B)

    f, b = feat[node], bin_[node]
    v = np.take_along_axis(bins, f[:, None], axis=1)[:, 0]
    right = v > b
    if full:
        right = np.where(v == 0, dir_[node] > 0, right)
        right = np.where(cat[f], (v == b) & (b != B - 1), right)
    assert got.shape == (N,) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), node * 2 + right)
    if n_nodes >= 4:
        assert (np.asarray(got)[node == n_nodes - 1]
                == 2 * (n_nodes - 1)).all()     # frozen: all left


def test_sliced_routing_grows_the_trees_of_the_whole_table_form(
        rng, monkeypatch):
    """Three trees of a fixed seed, levels 0-3 on sliced columns and
    level 4 on the whole table: trees and margins to the bit those of
    the whole-table form at every level (the step before PR 45), of
    ``predict_tree`` and of ``GBDTServable``'s host router."""
    from ytk_mp4j_tpu.models import gbdt

    N, F, B = 500, 12, 16
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=5, loss="logistic",
                     missing_bin=True, categorical_features=(3,),
                     learning_rate=0.3)
    assert [gbdt.route_sliced(2 ** d, F) for d in range(5)] \
        == [True, True, True, True, False]
    bins = rng.integers(1, B, (N, F)).astype(np.int32)
    bins[rng.random((N, F)) < 0.3] = 0
    y = ((bins[:, 3] == 4) ^ (bins[:, 7] > 9) ^ (bins[:, 0] == 0))
    y = y.astype(np.float32)

    def train():
        tr = GBDTTrainer(cfg, mesh=make_mesh(1))
        trees, margins = tr.train(bins, y, n_trees=3, seed=7)
        return tr, [tuple(np.asarray(a) for a in t) for t in trees], \
            np.asarray(margins)[:N]

    tr, trees, margins = train()
    with monkeypatch.context() as m:
        m.setattr(gbdt, "route_sliced", lambda n_nodes, F: False)
        _, whole_trees, whole_margins = train()
    for tree, whole in zip(trees, whole_trees):
        for a, b in zip(tree, whole):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(margins, whole_margins)
    assert len({tuple(t[0]) for t in trees}) == 3   # three different trees

    serve = gbdt.servable(trees, cfg)
    replayed = None
    for tree in trees:
        leaves = np.asarray(predict_tree(jnp.asarray(bins), tree, cfg))
        np.testing.assert_array_equal(leaves, serve._route(bins, tree))
        replayed = tr._update_margins(jnp.asarray(bins), tree, replayed)
    np.testing.assert_array_equal(np.asarray(replayed), margins)


@pytest.mark.parametrize("missing_bin", [False, True])
def test_missing_direction_matches_oracle(rng, missing_bin):
    N, F, B = 512, 4, 8
    # min_split_gain > 0: a pure/empty node's mathematically-zero gain
    # rounds to a small positive in the device's f32 while the f64
    # oracle gets exactly 0; a common threshold freezes both the same
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, hist_mode="flat",
                     learning_rate=1.0, missing_bin=missing_bin,
                     min_split_gain=0.01)
    bins = rng.integers(1, B, (N, F)).astype(np.int32)
    missing = rng.random(N) < 0.3
    bins[missing, 0] = 0                   # bin 0 = the missing bucket
    # missing samples behave like HIGH values of f0 (the case where a
    # learned direction matters: an ordered split at b >= 1 wants the
    # missing bucket on its RIGHT side, which forced-left cannot do;
    # splitting at b = 0 instead would mis-pool missing with the lows)
    y = (((bins[:, 0] >= B // 2) | missing) * 2.0
         + 0.01 * rng.standard_normal(N)).astype(np.float32)
    g = (np.zeros(N) - y).astype(np.float64)   # squared loss at preds=0
    h = np.ones(N, np.float64)
    of, ob, od, ol = _oracle_tree(bins, g, h, cfg)
    new_preds, (tf, tb, td, lv) = _train_one(bins, y, cfg)
    np.testing.assert_array_equal(tb, ob)
    # frozen nodes (bin == B-1) keep an arbitrary argmax feature on the
    # device (routing ignores it); compare features on real splits only
    live = ob != B - 1
    np.testing.assert_array_equal(tf[live], of[live])
    np.testing.assert_array_equal(td[live], od[live])
    np.testing.assert_allclose(lv, ol, rtol=1e-4, atol=1e-5)
    if missing_bin:
        assert (td > 0).any(), "signal-bearing missing should go right"
    else:
        assert (td == 0).all()


def test_missing_direction_improves_fit(rng):
    """Learned direction must beat forced-left on data where missing
    correlates with the target."""
    N, F, B = 1024, 3, 8
    bins = rng.integers(1, B, (N, F)).astype(np.int32)
    missing = rng.random(N) < 0.4
    bins[missing, 0] = 0
    y = (missing * 3.0
         + 0.05 * rng.standard_normal(N)).astype(np.float32)
    mses = {}
    for mb in (False, True):
        cfg = GBDTConfig(n_features=F, n_bins=B, depth=2,
                         hist_mode="flat", learning_rate=1.0,
                         missing_bin=mb)
        new_preds, _ = _train_one(bins, y, cfg)
        mses[mb] = float(np.mean((new_preds - y) ** 2))
    assert mses[True] <= mses[False] * 1.0001


def test_categorical_split_matches_oracle(rng):
    N, F, B = 512, 3, 8
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, hist_mode="flat",
                     learning_rate=1.0, categorical_features=(0,),
                     min_split_gain=0.01)
    bins = rng.integers(0, B - 1, (N, F)).astype(np.int32)
    # y depends on f0 == 3 EXACTLY — an ordered split cannot isolate it
    # in one level; the equality split can
    y = ((bins[:, 0] == 3) * 2.0
         + 0.01 * rng.standard_normal(N)).astype(np.float32)
    g = (np.zeros(N) - y).astype(np.float64)
    h = np.ones(N, np.float64)
    of, ob, od, ol = _oracle_tree(bins, g, h, cfg)
    new_preds, (tf, tb, td, lv) = _train_one(bins, y, cfg)
    np.testing.assert_array_equal(tb, ob)
    live = ob != B - 1          # frozen nodes keep an arbitrary feature
    np.testing.assert_array_equal(tf[live], of[live])
    np.testing.assert_allclose(lv, ol, rtol=1e-4, atol=1e-5)
    # the root must be the equality split on (f0, category 3)
    assert tf[0] == 0 and tb[0] == 3
    mse = float(np.mean((new_preds - y) ** 2))
    assert mse < 0.01


def test_categorical_beats_numeric_on_equality_signal(rng):
    N, F, B = 1024, 2, 16
    bins = rng.integers(0, B - 1, (N, F)).astype(np.int32)
    y = ((bins[:, 0] == 7) * 1.0
         + 0.02 * rng.standard_normal(N)).astype(np.float32)
    mses = {}
    for cats in ((), (0,)):
        cfg = GBDTConfig(n_features=F, n_bins=B, depth=1,
                         hist_mode="flat", learning_rate=1.0,
                         categorical_features=cats)
        new_preds, _ = _train_one(bins, y, cfg)
        mses[cats] = float(np.mean((new_preds - y) ** 2))
    assert mses[(0,)] < mses[()] * 0.5


def test_missing_and_categorical_roundtrip_predict(rng, tmp_path):
    """predict_tree replays training-time routing (missing + cat), and
    the dir array survives save/load."""
    N, F, B = 256, 4, 8
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, hist_mode="flat",
                     missing_bin=True, categorical_features=(2,),
                     learning_rate=0.7, n_trees=2)
    bins = rng.integers(1, B - 1, (N, F)).astype(np.int32)
    bins[rng.random(N) < 0.3, 0] = 0
    y = (bins[:, 2] == 2) * 1.5 + (bins[:, 0] == 0) * 1.0
    y = y.astype(np.float32)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    trees, preds = tr.train(bins, y)
    re_pred = tr.predict(bins, trees)
    np.testing.assert_allclose(re_pred, preds[:N], rtol=1e-4, atol=1e-5)
    path = str(tmp_path / "m.npz")
    tr.save_model(path, trees)
    cfg2, trees2, _ = GBDTTrainer.load_model(path)
    assert cfg2.missing_bin and cfg2.categorical_features == (2,)
    tr2 = GBDTTrainer(cfg2, mesh=make_mesh(1))
    np.testing.assert_allclose(tr2.predict(bins, trees2), re_pred,
                               rtol=1e-5)


def test_binner_missing_bucket(rng):
    from ytk_mp4j_tpu.models.binning import QuantileBinner
    X = rng.standard_normal((500, 3)).astype(np.float32)
    X[rng.random(500) < 0.2, 0] = np.nan
    b = QuantileBinner(8, missing_bucket=True).fit(X)
    out = b.transform(X)
    nan_mask = np.isnan(X)
    assert (out[nan_mask] == 0).all()
    assert (out[~nan_mask] >= 1).all() and (out[~nan_mask] < 8).all()
    # default mode: bin 0 shared between NaN and the lowest quantile
    b0 = QuantileBinner(8).fit(X)
    out0 = b0.transform(X)
    assert (out0[nan_mask] == 0).all()
    assert (out0[~nan_mask] == 0).any()


def test_missing_bin_learns_at_zero_reg(rng):
    """reg_lambda=0: the b=0 missing-right variant is an empty-left
    0/0 = NaN that must not poison argmax and freeze every node."""
    N, F, B = 512, 3, 8
    bins = rng.integers(1, B, (N, F)).astype(np.int32)
    bins[rng.random(N) < 0.3, 0] = 0
    y = (bins[:, 0] / B).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, hist_mode="flat",
                     learning_rate=1.0, missing_bin=True, reg_lambda=0.0)
    new_preds, (tf, tb, td, lv) = _train_one(bins, y, cfg)
    assert (tb != B - 1).any(), "all nodes frozen: NaN poisoned argmax"
    assert float(np.mean((new_preds - y) ** 2)) < 0.5 * float(np.var(y))


def test_load_model_without_dir_arrays(tmp_path, rng):
    """Models saved before default-direction support (feat/bin/leaf
    triples) must still load, with all-left directions."""
    F, B = 3, 8
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, n_trees=1)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    bins = rng.integers(0, B, (64, F)).astype(np.int32)
    y = (bins[:, 0] / B).astype(np.float32)
    trees, _ = tr.train(bins, y)
    path = str(tmp_path / "old.npz")
    tr.save_model(path, trees)
    # rewrite the file without the dir arrays (the old format)
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if not k.startswith("dir_")}
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    cfg2, trees2, _ = GBDTTrainer.load_model(path)
    for (tf, tb, td, lv), (of, ob, od, ol) in zip(trees2, trees):
        np.testing.assert_array_equal(td, 0)
        np.testing.assert_array_equal(tf, np.asarray(of))
    np.testing.assert_allclose(
        GBDTTrainer(cfg2, mesh=make_mesh(1)).predict(bins, trees2),
        tr.predict(bins, trees), rtol=1e-6)


def test_config_rejects_bad_categorical_types():
    from ytk_mp4j_tpu.exceptions import Mp4jError
    for bad in ((1.5,), ("x",), (True,)):
        with pytest.raises(Mp4jError):
            GBDTConfig(n_features=4, categorical_features=bad)
    # numpy integer indices normalize to plain ints
    cfg = GBDTConfig(n_features=4,
                     categorical_features=(np.int64(2), np.int32(0)))
    assert cfg.categorical_features == (2, 0)


def test_binner_missing_bucket_needs_three_bins():
    from ytk_mp4j_tpu.exceptions import Mp4jError
    from ytk_mp4j_tpu.models.binning import QuantileBinner
    with pytest.raises(Mp4jError):
        QuantileBinner(2, missing_bucket=True)
    QuantileBinner(3, missing_bucket=True)    # fine
    QuantileBinner(2)                         # fine without the bucket


def test_scanned_predict_matches_unrolled(rng):
    """predict scans over the stacked ensemble (one-tree program size);
    it must match the unrolled-loop formulation to 1 ulp (FMA fusion
    differs between program shapes, so exact bit-identity across XLA
    programs is not attainable)."""
    import jax

    cfg = GBDTConfig(n_features=7, n_bins=16, depth=4)
    T, N = 12, 500
    n_nodes, n_leaves = 2 ** cfg.depth - 1, 2 ** cfg.depth
    trees = [
        (jnp.asarray(rng.integers(0, cfg.n_features, n_nodes),
                     dtype=jnp.int32),
         jnp.asarray(rng.integers(0, cfg.n_bins, n_nodes),
                     dtype=jnp.int32),
         jnp.asarray(rng.integers(0, 2, n_nodes), dtype=jnp.int32),
         jnp.asarray(rng.standard_normal(n_leaves), dtype=jnp.float32))
        for _ in range(T)]
    bins = rng.integers(0, cfg.n_bins, (N, cfg.n_features)).astype(np.int32)
    tr = GBDTTrainer(cfg, n_devices=1)
    got = tr.predict(bins, trees)

    @jax.jit
    def unrolled(b, ts):
        out = jnp.zeros((b.shape[0],), jnp.float32)
        for t in ts:
            out = out + cfg.learning_rate * predict_tree(b, t, cfg)
        return out

    want = np.asarray(unrolled(jnp.asarray(bins), trees))
    np.testing.assert_allclose(got, want, atol=2e-7)


def test_scanned_predict_softmax_matches_unrolled(rng):
    import jax

    cfg = GBDTConfig(n_features=5, n_bins=8, depth=3, loss="softmax",
                     n_classes=3)
    T, N = 6, 300
    n_nodes, n_leaves = 2 ** cfg.depth - 1, 2 ** cfg.depth
    trees = [
        tuple(
            (jnp.asarray(rng.integers(0, cfg.n_features, n_nodes),
                         dtype=jnp.int32),
             jnp.asarray(rng.integers(0, cfg.n_bins, n_nodes),
                         dtype=jnp.int32),
             jnp.asarray(rng.integers(0, 2, n_nodes), dtype=jnp.int32),
             jnp.asarray(rng.standard_normal(n_leaves), dtype=jnp.float32))
            for _ in range(cfg.n_classes))
        for _ in range(T)]
    bins = rng.integers(0, cfg.n_bins, (N, cfg.n_features)).astype(np.int32)
    tr = GBDTTrainer(cfg, n_devices=1)
    got = tr.predict(bins, trees)

    @jax.jit
    def unrolled(b, ts):
        out = jnp.zeros((b.shape[0], cfg.n_classes), jnp.float32)
        for per_class in ts:
            out = out + cfg.learning_rate * jnp.stack(
                [predict_tree(b, t, cfg) for t in per_class], axis=1)
        return out

    want = np.asarray(unrolled(jnp.asarray(bins), trees))
    np.testing.assert_allclose(got, want, atol=2e-7)


# ------------------------------------------------- train_raw (consumer)
def _raw_problem(rng, n=400, f=6):
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    return X, y


def test_train_raw_matches_manual_wiring(rng):
    """train_raw == QuantileBinner.fit + transform + train with the
    same seed, and the fitted
    binner is retained for predict_raw."""
    from ytk_mp4j_tpu.models.binning import QuantileBinner

    X, y = _raw_problem(rng)
    cfg = GBDTConfig(n_features=6, n_bins=16, depth=3, n_trees=3,
                     learning_rate=0.5)
    tr = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees, margins = tr.train_raw(X, y, seed=7)

    manual_binner = QuantileBinner(16).fit(X, seed=7)
    bins = manual_binner.transform(X)
    tr2 = GBDTTrainer(cfg, mesh=make_mesh(4))
    trees2, margins2 = tr2.train(bins, y, seed=7)
    np.testing.assert_array_equal(tr.binner_.edges, manual_binner.edges)
    np.testing.assert_allclose(margins[:len(y)], margins2[:len(y)],
                               rtol=1e-6, atol=1e-7)
    for t1, t2 in zip(trees, trees2):
        for a1, a2 in zip(t1, t2):
            np.testing.assert_array_equal(np.asarray(a1),
                                          np.asarray(a2))
    # predict_raw rides the retained binner
    np.testing.assert_allclose(
        tr.predict_raw(X, trees), tr2.predict(bins, trees2),
        rtol=1e-6, atol=1e-7)
    # and actually learned the function
    pred = tr.predict_raw(X, trees)
    assert np.corrcoef(pred, y)[0, 1] > 0.8


def test_train_raw_missing_and_weights(rng):
    """NaN features flow to the missing bucket (cfg.missing_bin pairs
    with binner missing_bucket) and sample_weight reaches BOTH the
    sketch and the boosting gradients."""
    X, y = _raw_problem(rng)
    X[::5, 2] = np.nan
    cfg = GBDTConfig(n_features=6, n_bins=16, depth=3, n_trees=2,
                     missing_bin=True, learning_rate=0.5)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    w = np.where(y > 0, 3.0, 1.0).astype(np.float32)
    trees, _ = tr.train_raw(X, y, seed=3, sample_weight=w)
    assert tr.binner_.missing_bucket
    assert np.isfinite(tr.predict_raw(X, trees)).all()
    # weighted vs unweighted edges differ (the sketch saw the weights)
    trU = GBDTTrainer(cfg, mesh=make_mesh(2))
    trU.train_raw(X, y, seed=3)
    assert not np.array_equal(tr.binner_.edges, trU.binner_.edges)


def test_train_raw_eval_set_and_persistence(rng, tmp_path):
    """eval_set takes RAW features; save_model persists the train_raw
    binner by default and load_model returns it serving-ready."""
    X, y = _raw_problem(rng, n=600)
    Xt, yt, Xv, yv = X[:400], y[:400], X[400:], y[400:]
    cfg = GBDTConfig(n_features=6, n_bins=16, depth=3, n_trees=10,
                     learning_rate=0.3)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, _ = tr.train_raw(Xt, yt, seed=1, eval_set=(Xv, yv),
                            early_stopping_rounds=3)
    assert len(tr.eval_history_) >= 1
    path = str(tmp_path / "raw_model.npz")
    tr.save_model(path, trees)            # binner rides along
    cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
    assert binner2 is not None
    tr2 = GBDTTrainer(cfg2, mesh=make_mesh(2))
    tr2.binner_ = binner2
    np.testing.assert_allclose(tr2.predict_raw(Xv, trees2),
                               tr.predict_raw(Xv, trees),
                               rtol=1e-6, atol=1e-7)


def test_train_raw_distributed_binning(rng):
    """train_raw(comm=...) fits the binner via fit_distributed over
    the comm: every rank ends with identical edges equal to the merged
    sketch; predict stays rank-identical."""
    from helpers import run_slaves
    from ytk_mp4j_tpu.models.binning import QuantileBinner

    X, y = _raw_problem(rng)
    cfg = GBDTConfig(n_features=6, n_bins=8, depth=2, n_trees=2,
                     learning_rate=0.5)

    def job(slave, rank):
        tr = GBDTTrainer(cfg, mesh=make_mesh(1))
        trees, _ = tr.train_raw(X, y, seed=2, comm=slave)
        return tr.binner_.edges, tr.predict_raw(X[:16], trees)

    results = run_slaves(2, job)
    (e0, p0), (e1, p1) = results
    np.testing.assert_array_equal(e0, e1)
    np.testing.assert_allclose(p0, p1, rtol=1e-6, atol=1e-7)
    # replicated data on both ranks pools to the single-host sketch
    b = QuantileBinner(8)
    sk = b.local_sketch(X, sample=1_000_000, seed=2)
    b.merge_sketches(np.stack([sk.values] * 2),
                     np.stack([sk.counts] * 2),
                     np.stack([sk.finite] * 2),
                     cdf_stack=np.stack([sk.cdf] * 2))
    np.testing.assert_allclose(e0, b.edges, rtol=1e-6, atol=1e-6)


def test_train_raw_rejects_incompatible_binner(rng):
    """A FINER pre-fitted binner would emit bin ids the histogram
    one-hot silently drops; mismatched missing-bucket conventions
    silently reroute NaN — both must be errors."""
    from ytk_mp4j_tpu.exceptions import Mp4jError
    from ytk_mp4j_tpu.models.binning import QuantileBinner

    X, y = _raw_problem(rng, n=100)
    cfg = GBDTConfig(n_features=6, n_bins=16, depth=2, n_trees=1)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    with pytest.raises(Mp4jError, match="exceeds"):
        tr.train_raw(X, y, binner=QuantileBinner(64).fit(X))
    with pytest.raises(Mp4jError, match="missing_bucket"):
        tr.train_raw(X, y, binner=QuantileBinner(
            16, missing_bucket=True).fit(X))
    # coarser is legal (load_model's rule)
    trees, _ = tr.train_raw(X, y, binner=QuantileBinner(8).fit(X))
    assert np.isfinite(tr.predict_raw(X, trees)).all()
