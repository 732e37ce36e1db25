"""The scoring cell's arithmetic: the operations and bytes one batch
scoring job needs, computed from shapes (``arith.py`` is left as it is;
a PR that adds a cell adds a file).

The scoring program (``models/gbdt.py: score_shard``) selects, for every
row and every node of every tree, the bin of the node's split feature by
an MXU matmul of the table against a one-hot of the split features: a
tree of depth d takes 2**d one-hot rows (2**d - 1 nodes and one unused
slot). That matmul is all of the job's MXU work. What the job must move
through HBM whatever the order it walks rows and trees in: the staged
int32 table once, and the margins once.
"""

from __future__ import annotations


def score_select_flops(rows: int, n_features: int, depth: int,
                       n_trees: int) -> float:
    """MXU flops of one job's select: [rows, F] against [F, 2**depth] a
    tree, 2 * rows * F * 2**depth * trees."""
    return 2.0 * rows * n_features * 2 ** depth * n_trees


def score_min_bytes(rows: int, n_features: int) -> float:
    """Least bytes one job moves through HBM: the int32 table read once,
    the f32 margins written once."""
    return float(rows * n_features * 4 + rows * 4)
