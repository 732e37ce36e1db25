"""Trees grown leaf by leaf (``GBDTConfig(grow_policy="loss",
max_leaves=...)``, ISSUE 53), held to a plain float64 numpy grower that
lives here: bincount histograms from the rows of *every* node (no
sibling subtraction, no kernel), gains by the formula in
``benchmark/reference/gbdt_missing.py``'s docstring, the trainer's tie
rules (the first candidate in (feature, bin, direction) order among
equal gains of a node; the lowest heap index among equal gains of open
leaves)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ytk_mp4j_tpu.models.gbdt as gbdt
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTServable, GBDTTrainer,
                                      train_tree_shard)
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.parallel import make_mesh
from ytk_mp4j_tpu.utils import tuning

N, F, B = 2048, 6, 16


# ----------------------------------------------------------------------
# the reference: float64, one node at a time
# ----------------------------------------------------------------------
def np_best_split(bins, g, h, rows, cfg, feat_mask=None):
    """(gain, feature, bin, direction) of the best candidate of the node
    that holds ``rows``; gain -inf where it has none."""
    lam, mch = cfg.reg_lambda, cfg.min_child_hessian
    cats = set(cfg.categorical_features)
    best = (-np.inf, 0, 0, 0)
    for f in range(cfg.n_features):
        if feat_mask is not None and not feat_mask[f]:
            continue
        hg = np.bincount(bins[rows, f], weights=g[rows], minlength=cfg.n_bins)
        hh = np.bincount(bins[rows, f], weights=h[rows], minlength=cfg.n_bins)
        Gt, Ht = hg.sum(), hh.sum()
        for b in range(cfg.n_bins - 1):     # the last bin is no candidate
            if f in cats:                   # bin == b goes right
                variants = [(Gt - hg[b], Ht - hh[b], 0)]
            else:                           # bins <= b go left
                GL, HL = hg[:b + 1].sum(), hh[:b + 1].sum()
                variants = [(GL, HL, 0)]
                if cfg.missing_bin and b > 0:   # bin 0 with the right
                    variants.append((GL - hg[0], HL - hh[0], 1))
            for GL, HL, d in variants:
                if mch > 0 and (HL < mch or Ht - HL < mch):
                    continue
                with np.errstate(invalid="ignore", divide="ignore"):
                    gain = (GL * GL / (HL + lam)
                            + (Gt - GL) ** 2 / (Ht - HL + lam)
                            - Gt * Gt / (Ht + lam))
                if gain > best[0]:          # NaN never wins
                    best = (gain, f, b, d)
    return best


def np_goes_right(bins, feature, bin_, direction, cfg):
    v = bins[:, feature]
    if feature in cfg.categorical_features:
        return v == bin_
    right = v > bin_
    if cfg.missing_bin:
        right = np.where(v == 0, direction > 0, right)
    return right


def np_grow(bins, g, h, cfg, feat_mask=None, built=None):
    """One leaf-wise tree. Returns (tree, order, rows_built): the heap
    tree ``(feature, bin, direction, leaf)``, the heap indices in the
    order they were split, and the rows a grower that builds the
    smaller child from rows would have read. ``built``, a list, takes
    the rows [N] bool of every split's smaller child (ties: the left),
    in the order of the splits."""
    depth, B_ = cfg.depth, cfg.n_bins
    n_internal = 2 ** depth - 1
    feat = np.zeros(n_internal, np.int64)
    bin_ = np.full(n_internal, B_ - 1, np.int64)
    dir_ = np.zeros(n_internal, np.int64)
    every = np.ones(bins.shape[0], bool)
    leaves = {0: (every, 0, np_best_split(bins, g, h, every, cfg,
                                          feat_mask))}
    order, rows_built = [], bins.shape[0]
    for _ in range(cfg.max_leaves - 1):
        able = [(-best[0], at) for at, (_, level, best) in leaves.items()
                if level < depth and best[0] > cfg.min_split_gain]
        if not able:
            break
        at = min(able)[1]           # greatest gain, then lowest heap index
        rows, level, (_, f, b, d) = leaves.pop(at)
        feat[at], bin_[at], dir_[at] = f, b, d
        order.append(at)
        right = rows & np_goes_right(bins, f, b, d, cfg)
        left = rows & ~right
        rows_built += min(left.sum(), right.sum())
        if built is not None:
            built.append(right if right.sum() < left.sum() else left)
        for child, held in ((2 * at + 1, left), (2 * at + 2, right)):
            leaves[child] = (held, level + 1, np_best_split(
                bins, g, h, held, cfg, feat_mask))
    leaf = np.zeros(2 ** depth)
    for at, (rows, level, _) in leaves.items():
        with np.errstate(invalid="ignore"):
            leaf[(at + 1 - 2 ** level) << (depth - level)] = (
                -g[rows].sum() / (h[rows].sum() + cfg.reg_lambda))
    return (feat, bin_, dir_, leaf), order, int(rows_built)


def np_leaf_of(tree, bins, cfg):
    """The leaf [rows] each row reaches by plain routing of a heap tree
    (a frozen node, bin B - 1, sends every row left)."""
    feat, bin_, dir_, _ = tree
    node = np.zeros(bins.shape[0], np.int64)
    start = 0
    for d in range(cfg.depth):
        right = np.zeros(bins.shape[0], bool)
        for n in range(2 ** d):
            k = start + n
            if bin_[k] == cfg.n_bins - 1:
                continue
            here = node == n
            right |= here & np_goes_right(bins, int(feat[k]), int(bin_[k]),
                                          int(dir_[k]), cfg)
        node = node * 2 + right
        start += 2 ** d
    return node


def np_gradients(margins, y, loss):
    if loss == "logistic":
        p = 1.0 / (1.0 + np.exp(-margins))
        return p - y, p * (1.0 - p)
    return margins - y, np.ones_like(margins)


def np_train(bins, y, cfg, n_trees, weight=None, built_rows=None):
    """Leaf-wise boosting in float64: (trees, margins, rows_built);
    ``built_rows``, a list, takes ``np_grow``'s ``built`` of each tree."""
    margins = np.zeros(bins.shape[0])
    trees, built = [], 0
    for _ in range(n_trees):
        g, h = np_gradients(margins, y.astype(np.float64), cfg.loss)
        if weight is not None:
            g, h = g * weight, h * weight
        children = []
        tree, _, rows = np_grow(bins, g, h, cfg, built=children)
        if built_rows is not None:
            built_rows.append(children)
        margins = margins + cfg.learning_rate * tree[3][
            np_leaf_of(tree, bins, cfg)]
        trees.append(tree)
        built += rows
    return trees, margins, built


def assert_same_tree(got, want, cfg, leaf_tol=2e-4):
    """Split for split and leaf for leaf: the same nodes split, on the
    same (feature, bin, direction); an unsplit node stands frozen."""
    tf, tb, td, lv = (np.asarray(a) for a in got)
    wf, wb, wd, wl = want
    np.testing.assert_array_equal(tb, wb)
    live = wb != cfg.n_bins - 1
    np.testing.assert_array_equal(tf[live], wf[live])
    np.testing.assert_array_equal(td[live], wd[live])
    assert (td[~live] == 0).all()
    np.testing.assert_allclose(lv, wl, rtol=leaf_tol, atol=leaf_tol)


def n_leaves(tree, cfg) -> int:
    return 1 + int((np.asarray(tree[1]) != cfg.n_bins - 1).sum())


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------
def table(seed=0, n=N, f=F, b=B, missing=False, categorical=False):
    """Bins and a score that leans on several columns with different
    weights (so that no two candidates tie) plus noise."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(1 if missing else 0, b - 1 if categorical else b,
                        (n, f)).astype(np.int32)
    x = bins / (b - 1.0)
    score = (1.7 * x[:, 0] - 1.1 * (x[:, 1] > 0.4) + 0.9 * x[:, 2] * x[:, 3]
             + 0.3 * np.sin(5 * x[:, 4]))
    if missing:
        gone = rng.random((n, f)) < 0.3
        bins[gone] = 0
        score = score + 1.3 * gone[:, 1]    # missing reads like a high bin
    if categorical:
        score = score + 1.5 * (bins[:, 5] == 3)
    score = score + 0.1 * rng.standard_normal(n)
    return bins, score


def labels(score, loss):
    if loss == "logistic":
        return (score > np.median(score)).astype(np.float32)
    return score.astype(np.float32)


VARIANTS = {
    "plain": {},
    "missing": {"missing_bin": True},
    "categorical": {"categorical_features": (5,)},
    "weights": {},
}


def config(loss="squared", **kw):
    base = dict(n_features=F, n_bins=B, depth=4, loss=loss,
                learning_rate=0.5, hist_mode="pair", grow_policy="loss",
                max_leaves=9, min_split_gain=1e-3)
    base.update(kw)
    return GBDTConfig(**base)


# ----------------------------------------------------------------------
# against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("loss", ["logistic", "squared"])
def test_trees_are_the_references(loss, variant, n_devices):
    """Two trees through ``GBDTTrainer.train``: the second grows from
    gradients that are no longer the labels'."""
    bins, score = table(missing=variant == "missing",
                        categorical=variant == "categorical")
    y = labels(score, loss)
    cfg = config(loss, **VARIANTS[variant])
    weight = None
    if variant == "weights":
        weight = np.random.default_rng(1).integers(1, 4, N).astype(
            np.float32)
    tr = GBDTTrainer(cfg, mesh=make_mesh(n_devices))
    trees, margins = tr.train(bins, y, n_trees=2, sample_weight=weight)
    want, want_margins, rows_built = np_train(
        bins, y, cfg, 2,
        None if weight is None else weight.astype(np.float64))
    for got, ref in zip(trees, want):
        assert_same_tree(got, ref, cfg)
        assert n_leaves(got, cfg) == cfg.max_leaves
    np.testing.assert_allclose(margins[:N], want_margins, rtol=1e-4,
                               atol=1e-4)
    assert tr.grow_stats_ == {"splits": 2 * (cfg.max_leaves - 1),
                              "rows_built": rows_built}
    if variant == "missing":
        assert any((np.asarray(t[2]) > 0).any() for t in trees)
    if variant == "categorical":
        root = trees[0]
        assert 5 in np.asarray(root[0])[np.asarray(root[1]) != B - 1]


@pytest.mark.parametrize("n_devices", [1, 4])
def test_softmax_agrees_a_class_at_a_time(n_devices):
    bins, score = table(seed=3)
    C = 3
    y = np.digitize(score, np.quantile(score, [1 / 3, 2 / 3])).astype(
        np.int32)
    cfg = config("softmax", n_classes=C, max_leaves=6)
    tr = GBDTTrainer(cfg, mesh=make_mesh(n_devices))
    trees, margins = tr.train(bins, y, n_trees=1)
    p = np.full((N, C), 1.0 / C)
    want_margins = np.zeros((N, C))
    built = 0
    for c in range(C):
        g = p[:, c] - (y == c)
        h = p[:, c] * (1.0 - p[:, c])
        ref, _, rows = np_grow(bins, g, h, cfg)
        assert_same_tree(trees[0][c], ref, cfg)
        want_margins[:, c] = cfg.learning_rate * ref[3][
            np_leaf_of(ref, bins, cfg)]
        built += rows
    np.testing.assert_allclose(margins[:N], want_margins, atol=1e-4)
    assert tr.grow_stats_ == {"splits": C * (cfg.max_leaves - 1),
                              "rows_built": built}


def test_uneven_rows_and_a_hierarchical_mesh_grow_the_same_trees():
    """Rows that do not divide by the shards (the last shard is padded
    with rows that weigh nothing) and a two-axis mesh."""
    from ytk_mp4j_tpu.parallel import make_hier_mesh

    bins, score = table(seed=5, n=N - 3)
    y = labels(score, "squared")
    cfg = config()
    want, _, _ = np_train(bins, y, cfg, 1)
    for mesh in (make_mesh(4), make_hier_mesh(2, 2)):
        trees, _ = GBDTTrainer(cfg, mesh=mesh).train(bins, y, n_trees=1)
        assert_same_tree(trees[0], want[0], cfg)


# ----------------------------------------------------------------------
# a split reads a slab of its child's rows (ISSUE 54)
# ----------------------------------------------------------------------
@pytest.fixture
def small_slabs(monkeypatch):
    """Slabs of 32 rows, so that a table of 2,048 rows has children
    under, at and over a slab and over many."""
    monkeypatch.setattr(gbdt, "_SLAB_ROWS", 32)
    return 32


def rows_read_by_hand(trees_children, rows, n_shards, slab):
    """The rows the passes of a job read, from the reference's children:
    the root's pass every row the mesh holds (the rows that fill the
    last shard too), a split's pass on every shard as many slabs as hold
    the shard's rows of the built child: none where it has none."""
    per = -(-rows // n_shards)
    total = 0
    for children in trees_children:
        total += per * n_shards
        for built in children:
            child = np.zeros(per * n_shards, bool)
            child[:rows] = built
            total += sum(-(-mine // slab) * slab
                         for mine in child.reshape(n_shards, per).sum(1))
    return int(total)


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("rows", [N, N - 3])
def test_a_split_reads_a_slab_of_its_childs_rows(small_slabs, rows,
                                                 n_devices):
    """Children of under one slab and of many, on one shard and on four
    (each takes the slabs its own rows need), with rows that do not
    divide by the shards: the reference's trees, its count of the rows
    built, and the slabs' rows by hand."""
    bins, score = table(seed=53, n=rows)
    y = labels(score, "squared")
    cfg = config(depth=6, max_leaves=14)
    tr = GBDTTrainer(cfg, mesh=make_mesh(n_devices))
    trees, margins = tr.train(bins, y, n_trees=2)
    # the rows that fill the last shard are rows of the step's (bins 0,
    # weight 0: they follow bin 0 and count where they land), so the
    # reference grows on the table as the mesh holds it
    fill = -rows % n_devices
    held = np.concatenate([bins, np.zeros((fill, F), np.int32)])
    weight = np.concatenate([np.ones(rows), np.zeros(fill)])
    children = []
    want, want_margins, rows_built = np_train(
        held, np.concatenate([y, np.zeros(fill, np.float32)]), cfg, 2,
        weight if fill else None, built_rows=children)
    for got, ref in zip(trees, want):
        assert_same_tree(got, ref, cfg)
    np.testing.assert_allclose(margins[:rows], want_margins[:rows],
                               rtol=1e-4, atol=1e-4)
    assert tr.grow_stats_ == {"splits": 2 * 13, "rows_built": rows_built}
    sizes = sorted({int(c.sum()) for t in children for c in t})
    assert sizes[0] <= 2 * 32 and sizes[-1] > 10 * 32  # few slabs and many
    assert tr.grow_rows_read_ == rows_read_by_hand(
        children, rows + fill, n_devices, small_slabs)
    # far fewer than the passes over the table they replace
    assert tr.grow_rows_read_ < 2 * (rows + 13 * rows // 2)


def test_a_step_that_splits_nothing_reads_no_slab(small_slabs):
    """Where no open leaf clears the threshold the built child is empty:
    the step's loop over the slabs has no trip and it changes nothing."""
    bins, score = table(seed=7)
    y = labels(score, "squared")
    cfg = config(max_leaves=16, min_split_gain=25.0)
    tr = GBDTTrainer(cfg, n_devices=1)
    trees, _ = tr.train(bins, y, n_trees=1)
    children = []
    want, order, rows_built = np_grow(bins, *np_gradients(
        np.zeros(N), y.astype(np.float64), "squared"), cfg, built=children)
    assert 2 <= len(order) + 1 < 16
    assert_same_tree(trees[0], want, cfg)
    assert tr.grow_stats_ == {"splits": len(order), "rows_built": rows_built}
    assert tr.grow_rows_read_ == rows_read_by_hand([children], N, 1,
                                                   small_slabs)


@pytest.mark.parametrize("held,slab", [(31, 32), (32, 32), (33, 64),
                                       (64, 64), (65, 96)])
def test_a_child_of_exactly_a_slabs_size_takes_that_slab(small_slabs, held,
                                                         slab):
    """One split whose smaller child holds ``held`` rows: a slab's own
    size still fits it and one row more takes a second."""
    rng = np.random.default_rng(59)
    bins = rng.integers(1, B, (N, F)).astype(np.int32)
    bins[:held, 0] = 0
    y = (5.0 * (bins[:, 0] == 0) + 0.01 * rng.standard_normal(N)).astype(
        np.float32)
    cfg = config(max_leaves=2)
    tr = GBDTTrainer(cfg, n_devices=1)
    trees, _ = tr.train(bins, y, n_trees=1)
    want, order, rows_built = np_grow(bins, *np_gradients(
        np.zeros(N), y.astype(np.float64), "squared"), cfg)
    assert order == [0] and rows_built == N + held
    assert_same_tree(trees[0], want, cfg)
    assert tr.grow_stats_ == {"splits": 1, "rows_built": N + held}
    assert tr.grow_rows_read_ == N + slab


def test_the_second_form_holds_every_cell():
    """``pack_rows`` and ``_unpack_rows`` are each other's inverse at a
    width that fills no word evenly, for 8-bit and for 16-bit bins."""
    rng = np.random.default_rng(61)
    for n_bins, width in ((256, 13), (1024, 5), (16, 6)):
        cells = rng.integers(0, n_bins, (300, width)).astype(np.int32)
        per, bin_words, words = gbdt.packed_shape(width, n_bins)
        assert per == (4 if n_bins <= 256 else 2)
        assert bin_words % 8 == 0 and words % 128 == 0 \
            and words >= bin_words
        packed = gbdt.pack_rows(jnp.asarray(cells), n_bins)
        assert packed.shape == (300, words) and packed.dtype == jnp.uint32
        rows = np.array([7, 7, 299, 0])
        back = gbdt._unpack_rows(packed[rows], width, n_bins)
        np.testing.assert_array_equal(np.asarray(back).T, cells[rows])


@pytest.mark.parametrize("marked", [0, 1, 129, 5000])
def test_ranks_find_the_marked_rows_in_order(marked):
    """``_kth_rows`` on ``_ranks``' counts gives the marked rows' numbers
    ascending, over more than one group of blocks, and some row of the
    table for a rank past the last."""
    rows = 128 * 128 + 77
    rng = np.random.default_rng(marked)
    mask = np.zeros(rows, bool)
    mask[rng.choice(rows, marked, replace=False)] = True
    if marked > 1:
        mask[[0, rows - 1]] = True
    counts = gbdt._ranks(jnp.asarray(mask))
    assert counts[2].shape == (2,)
    got = np.asarray(gbdt._kth_rows(
        counts, jnp.arange(marked + 50, dtype=jnp.int32), rows))
    want = np.flatnonzero(mask)
    np.testing.assert_array_equal(got[:len(want)], want)
    assert ((0 <= got) & (got < rows)).all()


# ----------------------------------------------------------------------
# the budget and the cap
# ----------------------------------------------------------------------
@pytest.mark.parametrize("max_leaves", [2, 5, 16])
def test_the_budget_binds_while_gains_allow(max_leaves):
    bins, score = table(seed=7)
    cfg = config(max_leaves=max_leaves, min_split_gain=0.0)
    trees, _ = GBDTTrainer(cfg, n_devices=1).train(
        bins, labels(score, "squared"), n_trees=1)
    assert n_leaves(trees[0], cfg) == max_leaves
    want, order, _ = np_grow(bins, *np_gradients(
        np.zeros(N), score.astype(np.float32).astype(np.float64),
        "squared"), cfg)
    assert len(order) == max_leaves - 1
    assert_same_tree(trees[0], want, cfg)


def test_min_split_gain_finishes_a_tree_early():
    """Where no open leaf clears the threshold the remaining steps do
    nothing: fewer leaves than the budget, the rest of the heap frozen,
    and the counts say how many steps split."""
    bins, score = table(seed=7)
    y = labels(score, "squared")
    cfg = config(max_leaves=16, min_split_gain=25.0)
    tr = GBDTTrainer(cfg, n_devices=1)
    trees, margins = tr.train(bins, y, n_trees=1)
    want, order, rows_built = np_grow(bins, *np_gradients(
        np.zeros(N), y.astype(np.float64), "squared"), cfg)
    assert 2 <= len(order) + 1 < 16
    assert n_leaves(trees[0], cfg) == len(order) + 1
    assert_same_tree(trees[0], want, cfg)
    assert tr.grow_stats_ == {"splits": len(order), "rows_built": rows_built}
    np.testing.assert_allclose(
        margins[:N], cfg.learning_rate * want[3][np_leaf_of(want, bins, cfg)],
        atol=1e-4)


def test_no_leaf_lies_deeper_than_depth():
    """Half the rows in the top bin of one column, a quarter in the
    next, and so on, the label alternating bin by bin: best-first
    follows one branch down, a level a split. ``depth`` stops it there
    and the budget goes to the leaf beside it."""
    rng = np.random.default_rng(11)
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    bins[:, 0] = 15 - np.minimum(rng.geometric(0.5, N) - 1, 15)
    y = (np.where(bins[:, 0] % 2 == 1, 1.0, -1.0) + 0.4 * (bins[:, 1] > 7)
         + 0.1 * rng.standard_normal(N)).astype(np.float32)
    g, h = np_gradients(np.zeros(N), y.astype(np.float64), "squared")
    for depth, chain in ((5, [0, 1, 3, 7]), (3, [0, 1, 3, 2])):
        cfg = config(depth=depth, max_leaves=5, min_split_gain=0.0)
        want, order, _ = np_grow(bins, g, h, cfg)
        assert order == chain
        trees, _ = GBDTTrainer(cfg, n_devices=1).train(bins, y, n_trees=1)
        assert_same_tree(trees[0], want, cfg)
        split = np.flatnonzero(np.asarray(trees[0][1]) != B - 1)
        np.testing.assert_array_equal(split, sorted(chain))


@pytest.mark.parametrize("missing_bin", [False, True])
def test_a_full_budget_grows_the_level_wise_tree(missing_bin):
    """``max_leaves = 2 ** depth`` with every gain positive: every node
    splits, in another order, and the tree is ``_build_tree``'s node for
    node."""
    bins, score = table(seed=13, missing=missing_bin)
    y = labels(score, "squared")
    kw = dict(depth=3, min_split_gain=0.0, missing_bin=missing_bin)
    level = config(grow_policy="level", max_leaves=None, **kw)
    loss = config(max_leaves=8, **kw)
    preds = jnp.zeros(N, jnp.float32)
    want_preds, want = train_tree_shard(jnp.asarray(bins), jnp.asarray(y),
                                        preds, level)
    got_preds, got = train_tree_shard(jnp.asarray(bins), jnp.asarray(y),
                                      preds, loss)
    assert (np.asarray(want[1]) != B - 1).all()     # every node split
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_preds), np.asarray(want_preds),
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# downstream takes the trees as they are
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def ensemble():
    bins, score = table(seed=17, missing=True)
    y = labels(score, "logistic")
    cfg = config("logistic", missing_bin=True, depth=5, max_leaves=11)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, margins = tr.train(bins, y, n_trees=3)
    return cfg, tr, bins, trees, margins


def test_predict_gives_the_trained_margins_back(ensemble):
    cfg, tr, bins, trees, margins = ensemble
    np.testing.assert_allclose(tr.predict(bins, trees), margins[:N],
                               rtol=1e-5, atol=1e-6)
    want = sum(cfg.learning_rate * np.asarray(t[3], np.float64)[
        np_leaf_of([np.asarray(a) for a in t], bins, cfg)] for t in trees)
    np.testing.assert_allclose(margins[:N], want, rtol=1e-5, atol=1e-5)


def test_save_and_load_keep_policy_and_trees(ensemble, tmp_path):
    cfg, tr, bins, trees, margins = ensemble
    path = str(tmp_path / "leafwise.npz")
    tr.save_model(path, trees)
    cfg2, trees2, _ = GBDTTrainer.load_model(path)
    assert cfg2 == cfg and cfg2.grow_policy == "loss" \
        and cfg2.max_leaves == 11
    np.testing.assert_allclose(
        GBDTTrainer(cfg2, n_devices=1).predict(bins, trees2), margins[:N],
        rtol=1e-5, atol=1e-6)


def test_the_servable_routes_a_leaf_wise_ensemble(ensemble):
    cfg, tr, bins, trees, margins = ensemble
    serve = GBDTServable(trees, cfg)
    got = sum(serve.partial_margins(bins[:256], rank, 2)
              for rank in range(2))
    np.testing.assert_allclose(np.asarray(got).reshape(-1),
                               margins[:256], rtol=1e-5, atol=1e-5)


def test_feature_importance_counts_the_splits_that_are_there(ensemble):
    cfg, tr, bins, trees, _ = ensemble
    imp = tr.feature_importance(trees)
    assert imp.shape == (F,) and abs(imp.sum() - 1.0) < 1e-12
    assert imp[0] > 0 and imp[1] > 0


# ----------------------------------------------------------------------
# the default histogram path, sampling, held-out data, raw features
# ----------------------------------------------------------------------
@pytest.mark.parametrize("hist_mode", ["pallas", "matmul", "flat"])
def test_other_histogram_modes_agree_with_pair(hist_mode):
    """The default ``hist_mode`` (interpreted here) holds its terms as
    a bf16 pair, 2^-16 a term: gains this far apart give the same tree,
    and the leaves agree within that precision."""
    bins, score = table(seed=19, missing=True)
    y = labels(score, "squared")
    trees = {}
    for mode in ("pair", hist_mode):
        cfg = config(missing_bin=True, hist_mode=mode)
        trees[mode], _ = GBDTTrainer(cfg, n_devices=1).train(bins, y,
                                                             n_trees=1)
    assert_same_tree(trees[hist_mode][0],
                     [np.asarray(a) for a in trees["pair"][0]], cfg,
                     leaf_tol=1e-4)


def test_sampling_composes_and_is_seeded():
    bins, score = table(seed=23)
    y = labels(score, "squared")
    cfg = config(subsample=0.7, colsample=0.5, max_leaves=6)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    a, _ = tr.train(bins, y, n_trees=2, seed=5)
    b, _ = tr.train(bins, y, n_trees=2, seed=5)
    c, _ = tr.train(bins, y, n_trees=2, seed=6)
    for x, z in zip(a, b):
        for u, v in zip(x, z):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    assert any((np.asarray(u) != np.asarray(v)).any()
               for x, z in zip(a, c) for u, v in zip(x, z))


def test_colsample_masks_what_the_reference_masks():
    """``feat_mask`` reaches every search of the grower: with the mask
    the step drew, the reference grows the same tree."""
    bins, score = table(seed=29)
    y = labels(score, "squared")
    cfg = config(colsample=0.5, max_leaves=6)
    key = jax.random.fold_in(jax.random.key(0), 0)
    _, mask = gbdt._sampling_masks(key, cfg, N, None)
    mask = np.asarray(mask)
    assert 0 < mask.sum() < F
    trees, _ = GBDTTrainer(cfg, n_devices=1).train(bins, y, n_trees=1,
                                                   seed=0)
    want, _, _ = np_grow(bins, *np_gradients(
        np.zeros(N), y.astype(np.float64), "squared"), cfg, feat_mask=mask)
    assert_same_tree(trees[0], want, cfg)
    split_on = np.asarray(trees[0][0])[np.asarray(trees[0][1]) != B - 1]
    assert mask[split_on].all()


def test_held_out_data_and_early_stopping_read_leaf_wise_trees():
    bins, score = table(seed=31)
    va_bins, va_score = table(seed=37, n=512)
    cfg = config(max_leaves=6, learning_rate=0.4)
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, _ = tr.train(bins, labels(score, "squared"), n_trees=4,
                        eval_set=(va_bins, labels(va_score, "squared")))
    assert len(tr.eval_history_) == 4
    assert tr.eval_history_[-1] < tr.eval_history_[0]
    np.testing.assert_allclose(
        tr._eval_metric(tr.predict(va_bins, trees),
                        labels(va_score, "squared")),
        tr.eval_history_[-1], rtol=1e-5)
    assert tr.grow_stats_["splits"] == 4 * 5


def test_train_raw_reaches_the_grower():
    rng = np.random.default_rng(41)
    X = rng.standard_normal((600, 6)).astype(np.float32)
    X[rng.random(X.shape) < 0.2] = np.nan
    y = (np.nan_to_num(X[:, 0]) + (np.nan_to_num(X[:, 1]) > 0)).astype(
        np.float32)
    cfg = config(missing_bin=True, max_leaves=5, hist_mode="pallas")
    tr = GBDTTrainer(cfg, n_devices=1)
    trees, margins = tr.train_raw(X, y, n_trees=2)
    assert all(n_leaves(t, cfg) == 5 for t in trees)
    np.testing.assert_allclose(tr.predict_raw(X, trees), margins[:600],
                               rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# the level-wise path is the one it was
# ----------------------------------------------------------------------
def test_the_default_policy_never_reaches_the_new_grower(monkeypatch):
    def refuse(*args, **kw):
        raise AssertionError("a level-wise step called _grow_tree")

    def no_second_form(*args, **kw):
        raise AssertionError("a level-wise job packed its table")

    monkeypatch.setattr(gbdt, "_grow_tree", refuse)
    monkeypatch.setattr(gbdt, "pack_rows", no_second_form)
    monkeypatch.setattr(GBDTTrainer, "_build_pack", no_second_form)
    bins, score = table(seed=43)
    y = labels(score, "logistic")
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, loss="logistic")
    assert cfg.grow_policy == "level" and cfg.max_leaves is None
    tr = GBDTTrainer(cfg, mesh=make_mesh(2))
    trees, margins = tr.train(bins, y, n_trees=2)
    assert len(trees) == 2 and tr.grow_stats_ == {}
    assert tr.grow_rows_read_ == 0 and tr._pack is None
    # the step takes and returns what it did: five arguments, nothing
    # donated, three results
    data = tr.shard_data(bins, y)
    lowered = tr._step.lower(*data, jax.random.key_data(jax.random.key(0)))
    assert len(jax.tree.leaves(lowered.args_info)) == 5
    assert not any(a.donated for a in jax.tree.leaves(lowered.args_info))
    assert len(lowered.out_info) == 3 and lowered.out_info[2] == ()
    np.testing.assert_allclose(tr.predict(bins, trees), margins[:N],
                               rtol=1e-5, atol=1e-6)
    # and a leaf-wise job does reach them: the second form in its
    # staging, the grower in its step
    with pytest.raises(AssertionError, match="packed its table"):
        GBDTTrainer(config(), n_devices=1).train(bins, y, n_trees=1)
    monkeypatch.undo()
    monkeypatch.setattr(gbdt, "_grow_tree", refuse)
    with pytest.raises(AssertionError, match="_grow_tree"):
        GBDTTrainer(config(), n_devices=1).train(bins, y, n_trees=1)


# ----------------------------------------------------------------------
# spans and counts
# ----------------------------------------------------------------------
def test_build_and_fetch_spans_carry_the_policy_and_the_counts():
    bins, score = table(seed=47)
    cfg = config(max_leaves=7, hist_mode="pallas")
    spans.configure(4096)       # a fresh ring; the job's own afterwards
    try:
        tr = GBDTTrainer(cfg, n_devices=1)
        tr.train(bins, labels(score, "squared"), n_trees=2)
        got = spans.snapshot()
    finally:
        spans.configure(tuning.span_ring_capacity())
    build, pack = [s[6] for s in got if s[0] == "mp4j.step.build"]
    assert pack == {"key": "gbdt_grow_pack"}    # once, with the first job
    assert build["grow_policy"] == "loss" and build["max_leaves"] == 7
    assert build["hist_radix"] == "1"       # one node a pass, 16 bins
    fetch, = [s[6] for s in got if s[0] == "mp4j.gbdt.fetch"]
    assert fetch == {"job": 0, **tr.grow_stats_,
                     "rows_read": tr.grow_rows_read_}
    assert fetch["splits"] == 12
    # each tree: the table for the root, one slab a split (no child of
    # 2,048 rows is larger)
    assert fetch["rows_read"] == 2 * N + 12 * gbdt._SLAB_ROWS
    # each tree: its rows for the root, at most half a leaf's a split
    assert 2 * N < fetch["rows_built"] <= 2 * N + 12 * (N // 2)


def test_lowered_step_holds_the_growers_scopes():
    bins, score = table(seed=47, n=256)
    tr = GBDTTrainer(config(max_leaves=5), mesh=make_mesh(2))
    data = tr.shard_data(bins, labels(score, "squared"))
    lowered = tr._build_step().lower(
        *data, jax.random.key_data(jax.random.key(0)),
        tr._build_pack()(data[0]))
    # the second form is a sixth argument the step only reads: nothing
    # donated, and the level-wise step's three results
    assert len(jax.tree.leaves(lowered.args_info)) == 6
    assert not any(a.donated for a in jax.tree.leaves(lowered.args_info))
    assert len(lowered.out_info) == 3 and len(lowered.out_info[2]) == 3
    text = lowered.as_text(debug_info=True)
    for scope in ("gbdt.grow.pick", "gbdt.grow.book", "gbdt.hist",
                  "gbdt.route", "gbdt.best_splits", "gbdt.leaf",
                  "gbdt.grow.book/gbdt.grow.compact"):
        assert f"{scope}" in text, scope
    # the compaction, the gather and the unpacking stand inside the book
    # round and the kernel's call outside both
    stacks = re.findall(r'"([^"]*gbdt\.grow\.compact[^"]*)"', text)
    assert stacks and all(re.search(
        r"gbdt\.grow\.book/gbdt\.grow\.compact", s) for s in stacks)
    assert not re.search(r'"[^"]*gbdt\.grow\.[^"]*gbdt\.hist', text)
    assert "gbdt.level." not in text
    # the splits are a loop of the program, not max_leaves - 1 copies
    assert text.count("stablehlo.while") >= 1
    assert text.count('loc("gbdt.grow.pick"') + text.count(
        '/gbdt.grow.pick"') <= 4


# ----------------------------------------------------------------------
# the configuration's checks
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(grow_policy="leaf"), "grow_policy must be"),
    (dict(grow_policy="loss"), "needs an int max_leaves"),
    (dict(grow_policy="loss", max_leaves=1), "max_leaves in \\[2"),
    (dict(grow_policy="loss", max_leaves=65, depth=6), "2\\*\\*depth = 64"),
    (dict(grow_policy="loss", max_leaves=8.0), "needs an int max_leaves"),
    (dict(grow_policy="loss", max_leaves=True), "needs an int max_leaves"),
    (dict(max_leaves=8), "needs grow_policy='loss'"),
    (dict(grow_policy="level", max_leaves=64), "needs grow_policy='loss'"),
])
def test_configuration_checks_raise(kw, match):
    with pytest.raises(Mp4jError, match=match):
        GBDTConfig(**kw)


def test_the_widest_budget_is_accepted():
    cfg = GBDTConfig(grow_policy="loss", max_leaves=128, depth=7)
    assert cfg.max_leaves == 128
    assert GBDTConfig(grow_policy="loss", max_leaves=np.int64(2),
                      depth=1).max_leaves == 2
