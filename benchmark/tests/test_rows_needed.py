"""The benchmark's own count of the rows a tree's histograms need
(``reference/gbdt_rows_needed.py``) on trees drawn by hand, its routers
against the references' own, and the two readers that price the count
(``hist_kernel_roofline``, ``gbdt_step_mfu``) on the trace recorded on
the chip: 100 where the kernel's time is the least time, and never over
100 while the count is a tree's own. By hand, like the rest of this
directory; no trainer runs."""

import json
import os

import numpy as np
import pytest

from benchmark import arith_grow, cells, traffic, xplane
from benchmark.reference import gbdt as dense_reference
from benchmark.reference import gbdt_leafwise, gbdt_missing, gbdt_raw
from benchmark.reference import gbdt_rows_needed as needed

from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
B = 8                       # bins of the hand-drawn trees; B - 1 freezes


def _heap(depth, splits):
    """A level-order heap of ``depth`` in which only ``splits`` ({heap
    index: (feature, bin, direction)}) are split; every other node is
    frozen (bin B - 1, direction 0)."""
    nodes = 2 ** depth - 1
    feat, bin_, dir_ = (np.zeros(nodes, np.int64),
                        np.full(nodes, B - 1, np.int64),
                        np.zeros(nodes, np.int64))
    for k, (f, b, d) in splits.items():
        feat[k], bin_[k], dir_[k] = f, b, d
    return feat, bin_, dir_, np.arange(2 ** depth, dtype=np.float32)


def _table(rows_of_bins):
    return np.asarray(rows_of_bins, np.int32)


def test_a_level_wise_heap_with_a_frozen_node():
    """Depth 2 on ten rows of one column, bins 1..7 present: the root
    splits 6 | 4, its left child 2 | 4, its right child is left whole.
    The root's histogram takes all 10 rows and of its two children the
    smaller one's takes 4; the level under them is the leaves', which
    no histogram is built for (their sums are the parents' prefix sums):
    10 + 4, whatever the children split. A trainer that builds its
    leaves' histograms too (``leaves_built``, the leaf-wise one's count)
    adds the smaller leaf of every split node, 2, and nothing under the
    frozen node."""
    bins = _table([[1], [1], [2], [2], [2], [2], [5], [5], [6], [7]])
    tree = _heap(2, {0: (0, 2, 0), 1: (0, 1, 0)})
    deepest = needed.deepest_leaf(tree, needed.binned(bins, False), 10, 2)
    assert deepest.tolist() == [0, 0, 1, 1, 1, 1, 2, 2, 2, 2]
    assert needed.rows_needed(deepest, 2) == 10 + 4
    assert needed.rows_needed(deepest, 2, leaves_built=True) == 10 + 4 + 2
    # the same tree with the right child split 1 | 3 ... 2 | 2: a tie,
    # either leaf costs the same rows where leaves are built at all
    tree = _heap(2, {0: (0, 2, 0), 1: (0, 1, 0), 2: (0, 5, 0)})
    deepest = needed.deepest_leaf(tree, needed.binned(bins, False), 10, 2)
    assert np.bincount(deepest, minlength=4).tolist() == [2, 4, 2, 2]
    assert needed.rows_needed(deepest, 2) == 10 + 4
    assert needed.rows_needed(deepest, 2, leaves_built=True) == 18
    assert needed.rows_needed_a_tree(
        [tree, _heap(2, {})], needed.binned(bins, False), 10, 2) == [14, 10]
    # a frozen root: every row goes left, the smaller child is empty
    assert needed.rows_needed_a_tree(
        [_heap(3, {})], needed.binned(bins, False), 10, 3) == [10]
    # depth 3 with the left child of the root frozen: 10 for the root, 4
    # for its smaller child, 2 under the right child (2 | 2, a tie) and
    # nothing under the frozen one; the deepest level's minima (0 | 2
    # and 1 | 1) are the leaves'
    tree = _heap(3, {0: (0, 2, 0), 2: (0, 5, 0), 5: (0, 4, 0), 6: (0, 6, 0)})
    deepest = needed.deepest_leaf(tree, needed.binned(bins, False), 10, 3)
    assert np.bincount(deepest, minlength=8).tolist() == [
        6, 0, 0, 0, 0, 2, 1, 1]
    assert needed.rows_needed(deepest, 3) == 10 + 4 + 2
    assert needed.rows_needed(deepest, 3, leaves_built=True) == 16 + 0 + 1
    # no row at all, a tree of depth 0, and one of depth 1 (a stump: the
    # root's histogram alone)
    assert needed.rows_needed(np.zeros(0, np.int64), 2) == 0
    assert needed.rows_needed(np.zeros(7, np.int64), 0) == 7
    assert needed.rows_needed(np.array([0, 0, 1]), 1) == 3
    assert needed.rows_needed(np.array([0, 0, 1]), 1, leaves_built=True) == 4


def test_missing_cells_go_by_the_stored_direction():
    """Bin 0 is the reserved bucket: under ``missing_bin`` its rows
    follow the node's direction; without it bin 0 is the lowest bin."""
    bins = _table([[0], [0], [0], [3], [4], [6]])
    left, right = (_heap(1, {0: (0, 3, d)}) for d in (0, 1))
    for tree, want in ((left, [0, 0, 0, 0, 1, 1]), (right, [1, 1, 1, 0, 1, 1])):
        got = needed.deepest_leaf(tree, needed.binned(bins, True), 6, 1)
        assert got.tolist() == want
        assert got.tolist() == gbdt_missing.leaf_of(tree, bins, 1).tolist()
    dense = needed.deepest_leaf(right, needed.binned(bins, False), 6, 1)
    assert dense.tolist() == [0, 0, 0, 0, 1, 1]
    assert needed.rows_needed(dense, 1, leaves_built=True) == 6 + 2
    assert needed.rows_needed(dense, 1) == 6


def test_a_grown_heap_needs_what_the_leaf_wise_reference_builds():
    """The heap of ``test_run_leafwise``'s hand-drawn tree (0, 2 and 5
    split, the rest frozen) on a seeded table: the count is
    ``gbdt_leafwise.rows_built`` with the rows the reference's own
    router finds, ties going left there and costing the same here: the
    leaf-wise trainer builds the smaller child of every split, the
    children at the deepest level too, so its count is ``leaves_built``;
    the strict need leaves those out."""
    rng = np.random.default_rng(57)
    bins = rng.integers(0, B, (5000, 3), dtype=np.int32)
    tree = _heap(3, {0: (0, 3, 1), 2: (1, 2, 0), 5: (2, 4, 1)})
    split, leaves = gbdt_leafwise.grown(tree, B)
    assert split == [0, 2, 5] and leaves == [1, 6, 11, 12]
    node = gbdt_leafwise.leaf_node_of(tree, bins, 3, B)
    rows = {k: int((node == k).sum()) for k in leaves}
    for k in reversed(split):
        rows[k] = rows[2 * k + 1] + rows[2 * k + 2]
    deepest = needed.deepest_leaf(tree, needed.binned(bins, True), 5000, 3)
    assert deepest.tolist() == gbdt_missing.leaf_of(tree, bins, 3).tolist()
    built = needed.rows_needed(deepest, 3, leaves_built=True)
    assert built == gbdt_leafwise.rows_built(split, rows)
    assert rows[0] == 5000 and 5000 < built <= 12500
    # node 5's children are at the deepest level: built, not needed
    assert built - needed.rows_needed(deepest, 3) == min(rows[11], rows[12])
    # an exact tie: four rows, two a side
    tie = _table([[1], [1], [5], [5]])
    assert gbdt_leafwise.built_from_rows([0], {0: 4, 1: 2, 2: 2}) == {
        0: True, 1: True, 2: False}
    assert needed.rows_needed(needed.deepest_leaf(
        _heap(1, {0: (0, 2, 0)}), needed.binned(tie, True), 4, 1), 1,
        leaves_built=True) == 6


def test_the_dense_router_is_the_dense_references():
    """Random full heaps on a table without a reserved bucket: a row's
    leaf is the one ``reference/gbdt.py: route_margins`` reads its leaf
    value at (leaf values are the leaves' own numbers)."""
    rng = np.random.default_rng(5701)
    bins = rng.integers(0, B, (70_000, 6), dtype=np.int32)  # two blocks
    for depth in (1, 4):
        nodes = 2 ** depth - 1
        tree = (rng.integers(0, 6, nodes), rng.integers(0, B - 1, nodes),
                np.zeros(nodes, np.int64),
                np.arange(2 ** depth, dtype=np.float32))
        got = needed.deepest_leaf(tree, needed.binned(bins, False),
                                  len(bins), depth, threads=3)
        want = dense_reference.route_margins([tree], bins, depth, 1.0)
        assert np.array_equal(got, want.astype(np.int64))
        count = needed.rows_needed(got, depth)
        assert len(bins) <= count <= len(bins) * (1 + (depth - 1) / 2)
        assert count <= needed.rows_needed(got, depth, leaves_built=True) \
            <= len(bins) * (1 + depth / 2)


def test_floats_route_as_their_reference_bins_do():
    """``raw``: a table of floats with NaN under ascending edges goes
    where the bins ``reference/gbdt_raw.py`` makes of it go, a split at
    bin 0 (present | missing), repeated and infinite edges and a frozen
    node included; no table of bins is made."""
    rng = np.random.default_rng(5702)
    x = np.round(rng.normal(size=(20_000, 4)), 1).astype(np.float32)
    x[rng.random(x.shape) < 0.6] = np.nan
    edges = np.sort(np.round(rng.normal(size=(4, B - 2)), 1), axis=1).astype(
        np.float32)
    edges[1, -1] = np.inf
    edges[2, 2] = edges[2, 3]
    bins = gbdt_raw.bins(x, edges)
    assert bins.max() == B - 1 and (bins == 0).sum() == np.isnan(x).sum()
    tree = _heap(3, {0: (0, 0, 0), 1: (1, B - 2, 0), 2: (2, 3, 1),
                     3: (3, 1, 0), 5: (1, 0, 0), 6: (2, 2, 1)})
    want = needed.deepest_leaf(tree, needed.binned(bins, True), len(x), 3)
    got = needed.deepest_leaf(tree, needed.raw(x, edges), len(x), 3)
    assert np.array_equal(got, want)
    assert len(np.unique(got)) >= 5
    assert needed.rows_needed(got, 3) == needed.rows_needed(want, 3)


# ---------------------------------------------------------- the two readers
def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    """The job recorded on the chip by PR 24: two trees of depth 6 on
    ``traffic.binned_table(7, 1_000_000, 28, 256)``, one chip."""
    trace = xplane.load(os.path.join(DATA, "gbdt_1m_2trees_scoped.xplane.pb"))
    t0, t1 = xplane.window_of(trace, "bench.slice")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    config = {"rows": 1_000_000, "n_features": 28, "n_bins": 256, "depth": 6}
    return {"trace": trace, "window_ns": (t0, t1), "peaks": peaks,
            "config": config, "chips": 1, "traffic": {}, "spans": {}}


def _read(metric, run, **counters):
    spec = _spec(metric)
    reader = cells.load_module(ROOT, "readers", spec["reader"])
    return reader.read(spec, {**run, "counters": counters})


def test_the_kernels_roofline_reads_100_where_its_time_is_the_least(recorded):
    t0, t1 = recorded["window_ns"]
    kernel_s = xplane.op_seconds(recorded["trace"],
                                 _spec("hist_kernel_roofline")["pattern"],
                                 t0, t1)
    assert kernel_s == pytest.approx(0.104155051)
    # the rows whose one-hot flops take the chip exactly that long
    rows = kernel_s * 197e12 / arith_grow.grow_hist_mxu_flops(1, 28, 256)
    assert _read("hist_kernel_roofline", recorded, jobs=1,
                 hist_rows_needed=rows) == pytest.approx(100.0)
    # the count is of all the slice's jobs, whatever their number; a
    # chip's share of the mesh's rows
    assert _read("hist_kernel_roofline", recorded, jobs=4,
                 hist_rows_needed=rows) == pytest.approx(100.0)
    assert _read("hist_kernel_roofline", {**recorded, "chips": 4}, jobs=1,
                 hist_rows_needed=rows) == pytest.approx(25.0)
    # nothing to read: no count, no trace, no kernel in it
    assert _read("hist_kernel_roofline", recorded, jobs=1) is None
    assert _read("hist_kernel_roofline", {**recorded, "trace": None}, jobs=1,
                 hist_rows_needed=rows) is None
    plain = xplane.load(os.path.join(DATA, "ffm_small_4chunks.xplane.pb"))
    assert _read("hist_kernel_roofline",
                 {**recorded, "trace": plain,
                  "window_ns": xplane.window_of(plain, "bench.slice")},
                 jobs=1, hist_rows_needed=rows) is None


def test_the_steps_share_reads_100_where_the_slice_is_the_least(recorded):
    t0, t1 = recorded["window_ns"]
    rows = (t1 - t0) / 1e9 * 197e12 / arith_grow.grow_hist_mxu_flops(
        1, 28, 256)
    assert _read("gbdt_step_mfu", recorded, jobs=1,
                 hist_rows_needed=rows) == pytest.approx(100.0)
    assert _read("gbdt_step_mfu", recorded, jobs=2,
                 hist_rows_needed=rows / 2) == pytest.approx(50.0)
    assert _read("gbdt_step_mfu", recorded, jobs=1) is None
    assert _read("gbdt_step_mfu", {**recorded, "trace": None}, jobs=1,
                 hist_rows_needed=rows) is None


@pytest.mark.parametrize("metric", ["hist_kernel_roofline", "gbdt_step_mfu"])
def test_neither_passes_100_on_a_trees_own_count(recorded, metric):
    """The recorded job's table, routed through trees drawn at random
    (the program's own trees are not on record, and any tree bounds it):
    no tree of depth 6 needs more than N (1 + 5 / 2) rows, at which the
    recorded kernel's and slice's times read far under 100; a share over
    100 would take a kernel faster than the MXU's peak at the one-hot
    contraction of the rows a tree cannot do without."""
    rng = np.random.default_rng(5703)
    bins, _y = traffic.binned_table(7, 1_000_000, 28, 256)
    trees = [(rng.integers(0, 28, 63), rng.integers(0, 255, 63),
              np.zeros(63, np.int64), np.zeros(64, np.float32))
             for _ in range(2)]
    per_tree = needed.rows_needed_a_tree(
        trees, needed.binned(bins, False), len(bins), 6)
    assert all(1_000_000 < n <= 3_500_000 for n in per_tree)
    own = _read(metric, recorded, jobs=1, hist_rows_needed=sum(per_tree))
    most = _read(metric, recorded, jobs=1, hist_rows_needed=7_000_000)
    assert 0 < own <= most < 100
    # 7M rows at 0.2911 ns over the recorded 104.2 ms of kernel, 146.0 ms
    # of slice
    assert most == pytest.approx(
        {"hist_kernel_roofline": 2.236 * 7 / 8,
         "gbdt_step_mfu": 1.595 * 7 / 8}[metric], rel=2e-3)
    # what the retired arithmetic read on this trace: the flops of the
    # level-wise kernel's own operand, 32 N rows a tree
    assert _read(metric, recorded, jobs=1,
                 hist_rows_needed=2 * 32 * 1_000_000) == pytest.approx(
        64 / 7 * most)
