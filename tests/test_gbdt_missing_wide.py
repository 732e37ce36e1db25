"""``GBDTTrainer.train`` with ``missing_bin=True`` on a wide table in
which most cells are missing (the shape of the benchmark's
``gbdt-bosch-968`` configuration, small): the default ``hist_mode`` takes
the features in blocks, both default directions are scored, and the
result is held to the benchmark's plain float64 reference, which imports
nothing from the package."""

import ast
import importlib.util
import os

import numpy as np
import pytest

import jax

import ytk_mp4j_tpu.ops.hist_kernel as hist_kernel
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTTrainer,
                                      build_histograms)
from ytk_mp4j_tpu.ops.hist_kernel import feature_blocks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, F, B, DEPTH, TREES, LR, LAMBDA = 4096, 200, 256, 4, 3, 0.1, 1.0


def _load(rel):
    path = os.path.join(ROOT, "benchmark", rel)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(rel)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("reference/gbdt_missing.py")
missing_table = _load("missing_table.py")


@pytest.fixture(scope="module")
def trained():
    bins, y = missing_table.missing_binned_table(3000000021, ROWS, F, B, 0.81)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=DEPTH, loss="logistic",
                     learning_rate=LR, reg_lambda=LAMBDA, missing_bin=True)
    assert cfg.hist_mode == "pallas"            # the default path
    trees, margins = GBDTTrainer(cfg, n_devices=1).train(bins, y,
                                                         n_trees=TREES)
    trees = [tuple(np.asarray(a) for a in t) for t in trees]
    return bins, y, trees, margins


@pytest.fixture(scope="module")
def second_tree(trained):
    """What the benchmark's check builds for the second tree: g and h in
    float64 after the first tree by the plain router, held in f32, and
    the float64 histograms of every node of the second tree."""
    bins, y, trees, _ = trained
    g, h = reference.gradients(
        reference.route_margins(trees[:1], bins, DEPTH, LR), y)
    g, h = g.astype(np.float32), h.astype(np.float32)
    levels, deepest = reference.tree_level_histograms(
        trees[1], bins, g.astype(np.float64), h.astype(np.float64), DEPTH,
        B, threads=2)
    return g, h, levels, deepest


def _kernel_prefix_sum_err(trained, second_tree, cfg):
    """The deepest level's histograms as the trainer builds them (left
    children, the other rows on the sentinel id), through
    ``build_histograms``, against the reference's."""
    bins = trained[0]
    g, h, levels, deepest = second_tree
    n_left = 2 ** (DEPTH - 2)
    ids = np.where(deepest % 2 == 0, deepest // 2, n_left).astype(np.int32)
    got_g, got_h = jax.jit(lambda b, g, h, i: build_histograms(
        b, g, h, i, n_left, cfg))(bins, g, h, ids)
    want_g, want_h, want_abs = (a[-1][0::2] for a in levels)
    return max(
        reference.prefix_sum_error(np.asarray(got_g), want_g, want_abs),
        reference.prefix_sum_error(np.asarray(got_h), want_h, want_h))


def test_the_table_is_wide_and_mostly_missing(trained):
    bins, y, _, _ = trained
    assert feature_blocks(F, B, 2 ** (DEPTH - 2)) == (104, 2)   # ragged: 104 + 96
    assert 0.80 < (bins == 0).mean() < 0.82
    assert bins[bins > 0].min() == 1 and bins.max() == B - 1
    assert y.mean() == 0.5


def test_root_split_is_the_references_best_candidate(trained):
    bins, y, trees, _ = trained
    hist_g, hist_h = reference.root_histograms(bins, y, B, threads=2)
    gain = reference.split_gains(hist_g, hist_h, LAMBDA)
    root = tuple(int(trees[0][k][0]) for k in range(3))
    assert reference.root_split_ok(gain, hist_g, hist_h, LAMBDA, *root)
    # here it is the very candidate, not a tie
    assert root == tuple(int(v) for v in np.unravel_index(
        np.argmax(gain), gain.shape))


def test_reference_histograms_are_the_plain_bincounts(trained):
    bins, y, _, _ = trained
    hist_g, hist_h = reference.root_histograms(bins, y, B, threads=2)
    g = 0.5 - y.astype(np.float64)
    for f in (0, 50, 99, 100, F - 1):
        np.testing.assert_array_equal(
            hist_g[f], np.bincount(bins[:, f], weights=g, minlength=B))
        np.testing.assert_array_equal(
            hist_h[f], 0.25 * np.bincount(bins[:, f], minlength=B))


def test_margins_are_those_of_the_returned_trees(trained):
    """A plain router that sends a missing cell by its node's stored
    direction reproduces the margins; one that ignores the directions
    does not, so the directions are in use."""
    bins, y, trees, margins = trained
    want = reference.route_margins(trees, bins, DEPTH, LR)
    assert np.abs(margins[:ROWS] - want).max() <= 1e-5
    left = [(f, b, np.zeros_like(d), leaf) for f, b, d, leaf in trees]
    blind = reference.route_margins(left, bins, DEPTH, LR)
    assert np.abs(margins[:ROWS] - blind).max() > 1e-3
    assert reference.logloss(margins[:ROWS], y) < np.log(2.0)


def test_missing_goes_right_where_the_label_says_so(trained):
    """The last column's missing cells score like its high bins: its
    split sends them right, a partition no direction-0 split can make.
    That column lies in the kernel's last, ragged feature block."""
    _, _, trees, _ = trained
    assert list(missing_table.label_columns(F)) == [0, 50, 100, 149, F - 1]
    assert sum(int((t[2] > 0).sum()) for t in trees) >= 1
    on_last = [(int(b), int(d)) for f, b, d, _ in trees
               for f, b, d in zip(f, b, d) if f == F - 1 and b < B - 1]
    assert on_last, "no tree split on the column the label leans on"
    assert all(d == 1 and abs(b - B // 2) <= 8 for b, d in on_last), on_last
    # the root is that split, and both feature blocks decide splits
    assert int(trees[0][0][0]) == F - 1
    block = feature_blocks(F, B, 1)[0]
    split_on = {int(f) // block for t in trees
                for f, b in zip(t[0], t[1]) if b < B - 1}
    assert split_on == {0, 1}


def test_every_split_of_the_second_tree_is_the_references(trained,
                                                          second_tree):
    """With gradients that are no longer +-0.5, at every level (the
    kernel builds 1, 1, 2 and 4 nodes here) and with the right children
    derived from their parents: each node's split is the best float64
    candidate of the rows the tree itself sends there, within what the
    histograms' stated precision allows."""
    _, _, trees, _ = trained
    bad, checked = reference.tree_splits_ok(trees[1], second_tree[2], LAMBDA)
    assert checked == 2 ** DEPTH - 1 and bad == []
    # a split moved to a neighbouring column is found out
    moved = (trees[1][0].copy(),) + trees[1][1:]
    moved[0][2] = (moved[0][2] + 1) % F
    assert 2 in reference.tree_splits_ok(moved, second_tree[2], LAMBDA)[0]


def test_node_histograms_are_the_plain_bincounts(trained, second_tree):
    bins = trained[0]
    g, h, levels, deepest = second_tree
    for node, f in ((0, 0), (3, 100), (7, F - 1)):
        rows = deepest == node
        for k, w in enumerate((g, h, np.abs(g))):
            np.testing.assert_allclose(
                levels[k][-1][node, f],
                np.bincount(bins[rows, f], weights=w[rows].astype(np.float64),
                            minlength=B), rtol=0, atol=1e-9)
    # a level above is the sum of its children
    np.testing.assert_allclose(levels[0][0][0], levels[0][-1].sum(axis=0),
                               rtol=0, atol=1e-9)


def test_the_kernels_sums_keep_the_stated_precision(trained, second_tree):
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=DEPTH, missing_bin=True)
    err = _kernel_prefix_sum_err(trained, second_tree, cfg)
    assert 0 < err <= reference.HIST_REL_ERR, err


def test_a_kernel_without_the_lo_half_is_found_out(trained, second_tree,
                                                   monkeypatch):
    """The control of the benchmark's limit: the same kernel with the lo
    part of every term dropped (bf16 alone, the nearest precision below
    the stated one) is a hundred times outside it."""
    split = hist_kernel.split_bf16

    def hi_alone(a):
        hi, lo = split(a)
        return hi, jax.numpy.zeros_like(lo)

    monkeypatch.setattr(hist_kernel, "split_bf16", hi_alone)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=DEPTH, missing_bin=True)
    err = _kernel_prefix_sum_err(trained, second_tree, cfg)
    assert err > 100 * reference.HIST_REL_ERR, err


def test_the_error_a_right_child_carries():
    """A left child's histogram is built from rows; a right child's is
    its parent's less its sibling's and carries both their errors."""
    e = reference.HIST_REL_ERR
    root, kids = np.full((1, 1, 2), 8.0), np.array([[[2.0, 1.0]],
                                                     [[6.0, 7.0]]])
    (err_g, err_h) = reference.histogram_errors([root, kids], [root, kids])
    np.testing.assert_allclose(err_g[0], e * root)
    np.testing.assert_allclose(err_g[1][0], e * kids[0])
    np.testing.assert_allclose(err_g[1][1], e * (root[0] + kids[0]))
    np.testing.assert_allclose(err_h[1], err_g[1])


def test_a_node_left_whole_is_right_where_nothing_gains():
    hist_g = np.zeros((3, 8))
    hist_h = np.ones((3, 8))
    gain = reference.split_gains(hist_g, hist_h, LAMBDA)
    zero = np.zeros_like(hist_h)
    assert reference.split_ok(gain, hist_g, hist_h, zero, zero, LAMBDA,
                              0, 7, 0)
    hist_g[1, :4] = -1.0
    hist_g[1, 4:] = 1.0
    gain = reference.split_gains(hist_g, hist_h, LAMBDA)
    assert not reference.split_ok(gain, hist_g, hist_h, zero, zero, LAMBDA,
                                  0, 7, 0)
    assert reference.split_ok(gain, hist_g, hist_h, zero, zero, LAMBDA,
                              1, 3, 0)


def test_reference_imports_nothing_from_the_package():
    for rel in ("reference/gbdt_missing.py", "missing_table.py"):
        with open(os.path.join(ROOT, "benchmark", rel)) as f:
            tree = ast.parse(f.read())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert not any(n.split(".")[0] in ("ytk_mp4j_tpu", "jax")
                       for n in names), names
