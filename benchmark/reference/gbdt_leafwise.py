"""Plain numpy pieces in float64 that hold a tree grown leaf by leaf
(best-first, under a budget of leaves and a cap on depth) to the rule it
claims: the float64 histograms of every node of the tree from the rows
that reach it, what each was allowed to be off by, whether every split
is the best candidate of its node, and the *replay*: best-first growth
run again on those gains. Imports nothing from the system under test;
the histograms, the gains and their tolerances are
``reference/gbdt_missing.py``'s.

Conventions checked against (not imported from) ``models/gbdt.py`` with
``grow_policy="loss"``: a tree is ``gbdt_missing``'s level-order heap
``(feature [2^d - 1], bin [2^d - 1], direction [2^d - 1], leaf [2^d])``,
node k's children at 2k + 1 and 2k + 2; a node that was never split
stands as a frozen one (bin B - 1, direction 0: every row goes left), so
a leaf at depth k has its value at its level-local index shifted left by
``d - k``. Growth: the root's histogram is built from all rows; then,
``max_leaves - 1`` times, of the open leaves above depth ``d`` whose best
gain clears ``min_split_gain`` the one of greatest gain is split (ties:
the lowest heap index; none: the tree is finished); the child with fewer
rows (ties: the left) has its histogram built from the rows, its
sibling's is the parent's less it.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import gbdt_missing as missing

HIST_REL_ERR = missing.HIST_REL_ERR


def level_of(k: int) -> int:
    """Depth of heap node ``k`` (the root is 0)."""
    return (k + 1).bit_length() - 1


def grown(tree, n_bins: int):
    """(split, leaves): the heap indices the tree split, ascending, and
    its leaves: the children of split nodes that were not split
    themselves (the root alone where nothing was split). Raises
    ``ValueError`` where a split node hangs under an unsplit one: no
    growth produces that."""
    bin_ = np.asarray(tree[1])
    split = [int(k) for k in np.flatnonzero(bin_ != n_bins - 1)]
    inside = set(split)
    for k in split:
        if k and (k - 1) // 2 not in inside:
            raise ValueError(f"node {k} is split under an unsplit node")
    if not split:
        return [], [0]
    leaves = sorted(c for k in split for c in (2 * k + 1, 2 * k + 2)
                    if c not in inside)
    return split, leaves


def leaf_node_of(tree, bins: np.ndarray, depth: int, n_bins: int):
    """The heap index [rows] of the grown leaf each row of ``bins``
    reaches: plain routing (``gbdt_missing.leaf_of``) down to depth
    ``depth``, then up to the first node on the row's path that was not
    split."""
    deepest = missing.leaf_of(tree, bins, depth)
    is_split = np.zeros(2 ** (depth + 1) - 1, bool)
    is_split[: 2 ** depth - 1] = np.asarray(tree[1]) != n_bins - 1
    node = np.full(bins.shape[0], -1, np.int64)
    for d in range(depth + 1):
        here = (2 ** d - 1) + (deepest >> (depth - d))
        stop = (node < 0) & ~is_split[here]
        node[stop] = here[stop]
    return node


def tree_histograms(tree, bins: np.ndarray, g: np.ndarray, h: np.ndarray,
                    depth: int, n_bins: int, threads: int = 8):
    """The float64 histograms of every node of one grown tree, from
    gradients ``g``, ``h``: ``{heap index: (sum g, sum h, sum |g|)}``,
    each [F, B], every node holding the rows the tree's own splits send
    to it, ``{heap index: rows}``, and the leaf [rows] each row reaches
    (``leaf_node_of``). One pass over the table for the leaves (they
    part the rows); a split node is the sum of its children."""
    split, leaves = grown(tree, n_bins)
    node = leaf_node_of(tree, bins, depth, n_bins)
    slot = np.searchsorted(leaves, node)
    if not (np.asarray(leaves)[slot] == node).all():
        raise ValueError("a row stopped at a node that is no leaf")
    parts = missing.node_histograms(bins, g, h, slot, len(leaves), n_bins,
                                    threads)
    hists = {k: tuple(p[i] for p in parts) for i, k in enumerate(leaves)}
    rows = dict(zip(leaves, np.bincount(slot, minlength=len(leaves))))
    for k in reversed(split):           # children before their parent
        left, right = hists[2 * k + 1], hists[2 * k + 2]
        hists[k] = tuple(a + b for a, b in zip(left, right))
        rows[k] = rows[2 * k + 1] + rows[2 * k + 2]
    return hists, {k: int(v) for k, v in rows.items()}, node


def built_from_rows(split: list[int], rows: dict) -> dict:
    """{heap index: whether the node's histogram is built from rows}: the
    root's is; of a split node's children the one with fewer rows (ties:
    the left) is, and its sibling's is the parent's less it."""
    built = {0: True}
    for k in split:
        left, right = 2 * k + 1, 2 * k + 2
        built[left] = rows[left] <= rows[right]
        built[right] = not built[left]
    return built


def histogram_errors(split: list[int], hists: dict, built: dict) -> dict:
    """{heap index: (err_g, err_h)}, each [F, B]: what the histograms a
    trainer builds the way the configuration states may be off by, cell
    by cell. One built from rows is within ``HIST_REL_ERR`` of its terms'
    absolute sums (``gbdt_missing``'s bound); one taken as parent less
    sibling carries both their errors."""
    def own(k):
        return HIST_REL_ERR * hists[k][2], HIST_REL_ERR * hists[k][1]

    errs = {0: own(0)}
    for k in split:                     # ascending: parents first
        left, right = 2 * k + 1, 2 * k + 2
        small, other = (left, right) if built[left] else (right, left)
        errs[small] = own(small)
        errs[other] = tuple(p + s for p, s in zip(errs[k], errs[small]))
    return errs


def node_gains(tree, hists: dict, errs: dict, reg_lambda: float,
               split: list[int]):
    """For every node of ``hists``: the float64 gain of its best
    candidate and how far the trainer's reading of that gain may be off
    (``gbdt_missing.gain_tolerance`` at that candidate), ``{k: (gain,
    tolerance)}``; and the split nodes whose (feature, bin, direction)
    is not the best candidate of their node within what the histograms'
    errors allow (``gbdt_missing.split_ok``)."""
    feat, bin_, dir_ = (np.asarray(a) for a in tree[:3])
    best, bad = {}, []
    inside = set(split)
    for k, (hist_g, hist_h, _) in hists.items():
        gain = missing.split_gains(hist_g, hist_h, reg_lambda)
        top = tuple(int(v) for v in np.unravel_index(np.argmax(gain),
                                                     gain.shape))
        err_g, err_h = errs[k]
        best[k] = (float(gain[top]), missing.gain_tolerance(
            hist_g, hist_h, err_g, err_h, reg_lambda, *top))
        if k in inside and not missing.split_ok(
                gain, hist_g, hist_h, err_g, err_h, reg_lambda,
                int(feat[k]), int(bin_[k]), int(dir_[k])):
            bad.append(k)
    return best, sorted(bad)


def replay(split: list[int], best: dict, depth: int, max_leaves: int,
           min_split_gain: float = 0.0):
    """Best-first growth run again on the reference's gains, following
    the tree: at every step the open leaf of greatest gain among those
    the tree did split is taken, and no open leaf the tree left whole
    (above depth ``depth``) may gain more than it by more than both
    their tolerances. A taken leaf's gain clears ``min_split_gain``
    within its tolerance. When every split node has been taken and
    budget is left, no open leaf above ``depth`` may clear
    ``min_split_gain`` by more than its tolerance. Returns (order the
    split nodes were taken in, [(step, what broke)])."""
    inside = set(split)
    open_ = {0}
    order, broken = [], []
    while True:
        took = [k for k in sorted(open_) if k in inside]
        left_whole = [k for k in sorted(open_)
                      if k not in inside and level_of(k) < depth]
        if not took:
            if len(order) < max_leaves - 1:
                for u in left_whole:
                    gain, tol = best[u]
                    if gain > min_split_gain + tol:
                        broken.append((len(order), f"leaf {u} gains "
                                       f"{gain:.6g} and was left whole "
                                       f"with budget to spare"))
            break
        k = max(took, key=lambda n: (best[n][0], -n))
        gain, tol = best[k]
        if level_of(k) >= depth:
            broken.append((len(order), f"node {k} is split at depth "
                           f"{level_of(k)}"))
        if not gain > min_split_gain - tol:
            broken.append((len(order), f"node {k} is split for a gain of "
                           f"{gain:.6g}"))
        for u in left_whole:
            other, other_tol = best[u]
            if other > gain + tol + other_tol:
                broken.append((len(order), f"leaf {u} gains {other:.6g}, "
                               f"more than {gain:.6g} of node {k}, which "
                               f"was split in its place"))
        order.append(k)
        open_.remove(k)
        open_.update((2 * k + 1, 2 * k + 2))
    if len(order) > max_leaves - 1:
        broken.append((len(order), f"{len(order) + 1} leaves, over the "
                       f"budget of {max_leaves}"))
    return order, broken


def rows_built(split: list[int], rows: dict) -> int:
    """Rows whose histogram a grower that builds the smaller child reads
    for one tree: all of them for the root, the smaller child's for
    every split."""
    return rows[0] + sum(min(rows[2 * k + 1], rows[2 * k + 2])
                         for k in split)
