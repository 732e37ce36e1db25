"""The rows a boosted tree's histograms *have* to be built from, counted
from the returned tree and the host's table alone: plain routing, a
``bincount`` and a minimum. Imports nothing from the system under test
and asks it nothing.

A histogram is what a node's split is chosen from, so a node needs one
only where it may split. It is either summed over the rows that reach
the node or taken as its parent's less its sibling's, so of every
split's two children one has to be built from rows, and the least that
costs is the smaller one's rows; the root has no parent and costs all of
them. The children at a heap's deepest level are leaves: they split
nothing, their sums are prefix sums of the parent's histogram, and no
histogram is built for them (``models/gbdt.py: _build_tree`` builds
levels 0 to depth - 1). A level-wise tree of depth d therefore needs
``N + sum over its nodes above level d - 1 of min(rows left, rows
right)`` rows, at most ``N (1 + (d - 1) / 2)``, whatever the program
read to grow it: all N rows at every level, the left child and not the
smaller one, or a compacted slab of the child's own rows. A node that
was left whole stands in the heap as a frozen one (bin ``n_bins - 1``,
direction 0: every row goes left), its right side is empty and it adds
nothing.

The leaf-wise trainer (``_grow_tree``) builds the smaller child of
*every* split, one at the cap on depth or the last the budget of leaves
allows included, though such a child can never split; its counter
``grow_stats_["rows_built"]`` says so and ``reference/gbdt_leafwise.py:
rows_built`` counts the same. ``leaves_built=True`` adds the deepest
level's minima and is that count on a grown heap; the leaf-wise cell's
check holds the counter to it and its readers price it, so that cell's
shares credit the rows under the cap (``rows_built_at_the_cap`` in the
check's detail says how many) and are a little above the strict need.

``arith_grow.py`` prices a needed row (its one-hot flops and its bytes);
``readers/hist_kernel_roofline.py`` and ``readers/gbdt_step_mfu.py``
divide by the chip's peaks. While a histogram is a one-hot contraction
no program can choose a level-wise tree's splits from fewer rows, so in
the level-wise cells neither share can pass 100.

Conventions checked against (not imported from) ``models/gbdt.py``: a
tree is the level-order heap ``(feature [2^d - 1], bin [2^d - 1],
direction [2^d - 1], leaf [2^d])``; a row goes right where its bin is
above the node's; with a reserved missing bucket a missing cell (bin 0)
follows the node's stored direction instead. Rows are routed in blocks
on a few threads (numpy's gathers and compares release the GIL); the
count depends on the tree and the table alone.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_THREADS = 8
_BLOCK_ROWS = 65_536


def binned(bins: np.ndarray, missing_bin: bool):
    """How a row of a binned table [rows, F] goes at a node: right where
    its bin is above the node's; a missing cell (bin 0, where the table
    has the reserved bucket) by the node's stored direction."""
    def goes_right(rows, feat, bin_, dir_):
        value = bins[rows, feat]
        if not missing_bin:
            return value > bin_
        return np.where(value == 0, dir_ > 0, value > bin_)
    return goes_right


def raw(x: np.ndarray, edges: np.ndarray):
    """The same for a table of floats [rows, F] with NaN for an empty
    cell, under ``edges`` [F, E] ascending (``reference/gbdt_raw.py``):
    a value lands in bin ``1 + #{edges <= x}``, so its bin is above b
    where b is 0 or the b-th edge is at most x, and no bin is above
    E + 1 (a frozen node's); an empty cell is bin 0 and goes by the
    node's stored direction. No table of bins is made."""
    n_edges = edges.shape[1]

    def goes_right(rows, feat, bin_, dir_):
        value = x[rows, feat]
        edge = edges[feat, np.clip(bin_, 1, n_edges) - 1]
        with np.errstate(invalid="ignore"):
            above = (bin_ <= n_edges) & ((bin_ == 0) | (value >= edge))
        return np.where(np.isnan(value), dir_ > 0, above)
    return goes_right


def deepest_leaf(tree, goes_right, n_rows: int, depth: int,
                 threads: int = _THREADS) -> np.ndarray:
    """The leaf [rows] of the heap's deepest level each row reaches by
    plain routing; the node a row is in at level d is
    ``leaf >> (depth - d)``."""
    feat, bin_, dir_ = (np.asarray(a) for a in tree[:3])

    def route(lo: int) -> np.ndarray:
        rows = np.arange(lo, min(n_rows, lo + _BLOCK_ROWS))
        node = np.zeros(len(rows), np.int64)
        start = 0
        for d in range(depth):
            at = start + node
            node = node * 2 + goes_right(rows, feat[at], bin_[at], dir_[at])
            start += 2 ** d
        return node

    with ThreadPoolExecutor(threads) as pool:
        return np.concatenate(list(pool.map(
            route, range(0, n_rows, _BLOCK_ROWS))) or [np.zeros(0, np.int64)])


def rows_needed(deepest: np.ndarray, depth: int,
                leaves_built: bool = False) -> int:
    """Rows one tree's histograms have to be built from, given the
    deepest-level leaf of every row: all of them for the root and, under
    every node whose children may split, the smaller child's (a tie
    costs either; a node left whole has an empty right side and costs
    nothing). The children at the deepest level are leaves and cost
    nothing, unless ``leaves_built``: the leaf-wise trainer's own count,
    which builds them."""
    at_level = np.bincount(deepest, minlength=2 ** depth)
    needed = int(at_level.sum())
    for level in range(depth, 0, -1):       # the children's level
        if level < depth or leaves_built:
            needed += int(np.minimum(at_level[0::2], at_level[1::2]).sum())
        at_level = at_level[0::2] + at_level[1::2]
    return needed


def rows_needed_a_tree(trees, goes_right, n_rows: int, depth: int,
                       threads: int = _THREADS) -> list[int]:
    """``rows_needed`` of every level-wise tree of a job, in order."""
    return [rows_needed(deepest_leaf(tree, goes_right, n_rows, depth,
                                     threads), depth) for tree in trees]
