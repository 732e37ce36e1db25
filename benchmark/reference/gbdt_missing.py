"""Plain numpy GBDT pieces in float64 for tables with a reserved missing
bucket: the gain of every (feature, bin, direction) of a node from
bincount histograms, at the root and in every node of a later tree; the
error a histogram may have at the stated precision and what it allows a
split; a router that sends bin 0 by the node's stored direction;
logloss. Imports nothing from the system under test.

Conventions checked against (not imported from) ``models/gbdt.py`` with
``missing_bin=True``: bin 0 holds the missing cells of every feature;
logistic loss from zero margins gives g = 0.5 - y, h = 0.25; a split at
bin b sends present bins ``<= b`` left and ``> b`` right; direction 0
sends the node's missing cells left, direction 1 right; the last bin is
never a candidate, and neither is (bin 0, direction 1), whose left child
is empty by construction; gain is ``GL^2/(HL+l) + GR^2/(HR+l) -
G^2/(H+l)`` and a node no candidate of which gains anything is left whole
(bin B - 1, direction 0: every row goes left); below the root only the
left children's histograms are built from rows, a right child's is its
parent's less its sibling's; a tree is (feature [2^d - 1], bin [2^d - 1], direction
[2^d - 1], leaf value [2^d]) in level order and a row's margin gains
``learning_rate * leaf``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# What the configuration states of the histograms (f32 sums of terms held
# as a bf16 pair): hi keeps a term's top 8 significant bits by
# truncation, lo the next 8 of what is left, rounded, so a term t comes
# back within 2^-16 |t| (1.53e-5) and a sum S of terms t_i within
# 2^-16 * sum|t_i| whatever the number of terms; a sum of many is
# typically within 1e-6, where the f32 accumulation takes over. The
# bound, with a little room for that accumulation:
HIST_REL_ERR = 1.6e-5
_CHUNK_ROWS = 8_192


def root_histograms(bins: np.ndarray, y: np.ndarray, n_bins: int,
                    threads: int = 8):
    """(hist_g, hist_h), each [F, B] f64, of the root from zero margins.
    Counted, not summed: g is +-0.5 and h is 0.25, so a (feature, bin)
    cell's sums follow from how many rows, and how many positive rows,
    fall into it. Rows are taken in chunks that stay in the cache (a
    column of a row-major table does not)."""
    rows, n_features = bins.shape
    size = n_features * n_bins
    offsets = (np.arange(n_features) * n_bins).astype(bins.dtype)
    positive = np.asarray(y) > 0.5

    def count(lo: int):
        flat = bins[lo:lo + _CHUNK_ROWS] + offsets
        return (np.bincount(flat.ravel(), minlength=size),
                np.bincount(flat[positive[lo:lo + _CHUNK_ROWS]].ravel(),
                            minlength=size))

    every = np.zeros(size, np.int64)
    pos = np.zeros(size, np.int64)
    with ThreadPoolExecutor(threads) as pool:
        for a, b in pool.map(count, range(0, rows, _CHUNK_ROWS)):
            every += a
            pos += b
    every = every.reshape(n_features, n_bins).astype(np.float64)
    pos = pos.reshape(n_features, n_bins).astype(np.float64)
    return 0.5 * (every - pos) - 0.5 * pos, 0.25 * every


def split_gains(hist_g: np.ndarray, hist_h: np.ndarray,
                reg_lambda: float) -> np.ndarray:
    """Gain of every candidate split of one node ([F, B] histograms) or
    of several ([nodes, F, B]), [..., F, B, 2] f64: the last axis is the
    direction of the missing bucket (0 left, 1 right). Candidates that
    cannot be chosen are -inf."""
    gl0, hl0 = np.cumsum(hist_g, axis=-1), np.cumsum(hist_h, axis=-1)
    gt, ht = gl0[..., -1:], hl0[..., -1:]

    def score(gs, hs):
        return gs * gs / (hs + reg_lambda)

    def gain(gl, hl):
        return score(gl, hl) + score(gt - gl, ht - hl) - score(gt, ht)

    out = np.stack([gain(gl0, hl0),
                    gain(gl0 - hist_g[..., :1], hl0 - hist_h[..., :1])],
                   axis=-1)
    out[..., -1, :] = -np.inf
    out[..., 0, 1] = -np.inf
    return out


def gain_tolerance(hist_g, hist_h, err_g, err_h, reg_lambda: float,
                   feature: int, bin_: int, direction: int) -> float:
    """How far the gain of one candidate of one node can be off when
    every cell of the node's histograms ([F, B]) is within ``err_g`` /
    ``err_h`` ([F, B], ``histogram_errors``) of the truth. Errors add
    over the cells of a child, and for a child score s = G^2 / (H + l):
    |ds| <= (2 |G| dG + s dH) / (H + l); the candidate's three scores
    (left, right, parent) add up."""
    def left(a):
        v = a[feature, :bin_ + 1].sum()
        return v - a[feature, 0] if direction else v

    tol = 0.0
    for side in (left, lambda a: a[feature].sum() - left(a),
                 lambda a: a[feature].sum()):
        g, h, dg, dh = (side(a) for a in (hist_g, hist_h, err_g, err_h))
        tol += (2 * abs(g) * dg + g * g / (h + reg_lambda) * dh) \
            / (h + reg_lambda)
    return float(tol)


def split_ok(gain: np.ndarray, hist_g, hist_h, err_g, err_h,
             reg_lambda: float, feature: int, bin_: int,
             direction: int) -> bool:
    """Whether (feature, bin, direction) is the best candidate of a node
    (``gain`` [F, B, 2]), or ties it within what the histograms' error
    bounds allow: both gains may be off by their ``gain_tolerance``. A
    node the trainer left whole (bin B - 1: every row goes left) is right
    where no candidate gains anything."""
    best = tuple(int(v) for v in np.unravel_index(np.argmax(gain),
                                                  gain.shape))
    slack = gain_tolerance(hist_g, hist_h, err_g, err_h, reg_lambda, *best)
    if bin_ == gain.shape[1] - 1:
        return bool(gain[best] <= slack)
    slack += gain_tolerance(hist_g, hist_h, err_g, err_h, reg_lambda,
                            feature, bin_, direction)
    return bool(gain[feature, bin_, direction] >= gain[best] - slack)


def root_split_ok(gain: np.ndarray, hist_g, hist_h, reg_lambda: float,
                  feature: int, bin_: int, direction: int) -> bool:
    """``split_ok`` for the root from zero margins, where |g| = 0.5 = 2 h
    for every row: a cell's sum of |g| is twice its sum of h."""
    return split_ok(gain, hist_g, hist_h, HIST_REL_ERR * 2 * hist_h,
                    HIST_REL_ERR * hist_h, reg_lambda, feature, bin_,
                    direction)


def gradients(margins: np.ndarray, y: np.ndarray):
    """(g, h) f64 of the logistic loss at ``margins``."""
    p = 1.0 / (1.0 + np.exp(-margins.astype(np.float64)))
    return p - y, p * (1.0 - p)


def leaf_of(tree, bins: np.ndarray, depth: int) -> np.ndarray:
    """The leaf [rows] each row of ``bins`` reaches in one level-order
    tree ``(feature, bin, direction, leaf)`` by plain routing: a missing
    cell (bin 0) follows the stored direction of its node. The node a
    row is in at level d is ``leaf >> (depth - d)``."""
    feat, bin_, dir_, _ = tree
    rows = np.arange(bins.shape[0])
    node = np.zeros(bins.shape[0], np.int64)
    start = 0
    for d in range(depth):
        idx = start + node
        value = bins[rows, feat[idx]]
        right = np.where(value == 0, dir_[idx] > 0, value > bin_[idx])
        node = node * 2 + right
        start += 2 ** d
    return node


def node_histograms(bins: np.ndarray, g: np.ndarray, h: np.ndarray,
                    node: np.ndarray, n_nodes: int, n_bins: int,
                    threads: int = 8):
    """(hist_g, hist_h, hist_abs_g), each [n_nodes, F, B] f64: the sums
    of g, h and |g| over the rows of each node that fall into each
    (feature, bin) cell. A node's rows are taken a chunk at a time (one
    node's [F, B] sums stay in the cache; all the nodes' do not) and
    only the present cells are visited; a feature's missing bucket
    (bin 0) is what its node holds less what the feature's present
    cells hold."""
    n_features = bins.shape[1]
    size = n_features * n_bins
    weights = (g, h, np.abs(g))
    order = np.argsort(node, kind="stable")
    ends = np.searchsorted(node[order], np.arange(n_nodes + 1))
    tasks = [(n, order[lo:min(lo + _CHUNK_ROWS, ends[n + 1])])
             for n in range(n_nodes)
             for lo in range(ends[n], ends[n + 1], _CHUNK_ROWS)]

    def count(task):
        rows = task[1]
        flat = bins[rows].ravel()
        at = np.flatnonzero(flat)
        row = at // n_features
        cell = (at - row * n_features) * n_bins + flat[at]
        return [np.bincount(cell, weights=w[rows][row], minlength=size)
                for w in weights]

    out = [np.zeros((n_nodes, size)) for _ in weights]
    with ThreadPoolExecutor(threads) as pool:
        for (n, _), parts in zip(tasks, pool.map(count, tasks)):
            for hist, part in zip(out, parts):
                hist[n] += part
    for k, (hist, w) in enumerate(zip(out, weights)):
        hist = out[k] = hist.reshape(n_nodes, n_features, n_bins)
        held = np.bincount(node, weights=w, minlength=n_nodes)
        hist[:, :, 0] = held[:, None] - hist[:, :, 1:].sum(axis=2)
    return tuple(out)


def histogram_errors(abs_g_levels, h_levels):
    """Error bounds (err_g, err_h), a pair of lists over the levels, of
    the histograms a trainer builds the way the configuration states:
    a histogram built from rows is within ``HIST_REL_ERR`` of its terms'
    absolute sums cell by cell (the root's, and every left child's); a
    right child's is its parent's less its left sibling's, so it carries
    both their errors. ``abs_g_levels[d]`` / ``h_levels[d]``: the sums
    of |g| / h at level d, [2^d, F, B]."""
    errs = []
    for levels in (abs_g_levels, h_levels):
        out = [HIST_REL_ERR * levels[0]]
        for d in range(1, len(levels)):
            own = HIST_REL_ERR * levels[d]
            err = own.copy()
            err[1::2] = out[d - 1] + own[0::2]
            out.append(err)
        errs.append(out)
    return tuple(errs)


def tree_level_histograms(tree, bins: np.ndarray, g: np.ndarray,
                          h: np.ndarray, depth: int, n_bins: int,
                          threads: int = 8):
    """The float64 histograms of every node of one tree, from gradients
    ``g``, ``h``: three lists over the levels (sums of g, of h, of |g|),
    entry d [2^d, F, B], each node holding the rows the tree's own upper
    splits send to it; and the node [rows] each row is in at the deepest
    level. One pass over the table at the deepest level; a level above
    is the sum of its children."""
    deepest = leaf_of(tree, bins, depth) >> 1
    hists = node_histograms(bins, g, h, deepest, 2 ** (depth - 1), n_bins,
                            threads)
    return tuple([hist.reshape(2 ** d, -1, *hist.shape[1:]).sum(axis=1)
                  for d in range(depth)] for hist in hists), deepest


def tree_splits_ok(tree, levels, reg_lambda: float):
    """Holds every split of one tree to the float64 histograms of its
    nodes (``tree_level_histograms``). Returns (the nodes, in level
    order, whose split is not the best candidate within the stated
    histogram precision; nodes checked)."""
    feat, bin_, dir_, _ = tree
    hist_g, hist_h, hist_abs_g = levels
    err_g, err_h = histogram_errors(hist_abs_g, hist_h)
    bad, start = [], 0
    for d in range(len(hist_g)):
        for n in range(2 ** d):
            k = start + n
            gain = split_gains(hist_g[d][n], hist_h[d][n], reg_lambda)
            if not split_ok(gain, hist_g[d][n], hist_h[d][n],
                            err_g[d][n], err_h[d][n], reg_lambda,
                            int(feat[k]), int(bin_[k]), int(dir_[k])):
                bad.append(k)
        start += 2 ** d
    return bad, start


def prefix_sum_error(got: np.ndarray, want: np.ndarray,
                     want_abs: np.ndarray) -> float:
    """The largest error of ``got``'s sums over the bins ``<= b`` of a
    (node, feature), the quantities a split search reads, as a share of
    the absolute sum of the terms that entered them ([..., B] each; the
    share that ``HIST_REL_ERR`` bounds). Every such sum holds the
    feature's missing bucket, so none rests on a handful of rows."""
    off = np.abs(np.cumsum(got.astype(np.float64), axis=-1)
                 - np.cumsum(want, axis=-1))
    room = np.cumsum(want_abs, axis=-1)
    share = np.divide(off, room, out=np.where(off > 0, np.inf, 0.0),
                      where=room > 0)
    return float(share.max())


def route_margins(trees, bins: np.ndarray, depth: int,
                  learning_rate: float) -> np.ndarray:
    """Margins [rows] f64 of an ensemble of level-order trees, each
    ``(feature, bin, direction, leaf)``, by plain routing (``leaf_of``)."""
    margins = np.zeros(bins.shape[0])
    for tree in trees:
        margins += learning_rate * tree[3][leaf_of(tree, bins, depth)].astype(
            np.float64)
    return margins


def logloss(margins: np.ndarray, y: np.ndarray) -> float:
    z = margins.astype(np.float64)
    return float(np.mean(np.maximum(z, 0) - z * y
                         + np.log1p(np.exp(-np.abs(z)))))
