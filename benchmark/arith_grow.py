"""The arithmetic of a tree grown leaf by leaf (``grow_policy="loss"``),
kept with the benchmark like ``arith.py``: what the histograms of the
nodes that *had* to be built from rows cost, whatever the program read
to build them. The root's is built from all rows and, of every split's
two children, the smaller one's from its own rows (its sibling's is the
parent's less it); ``rows_built`` is their sum, counted by the program
(``GBDTTrainer.grow_stats_``) and held to the reference's count by the
cell's check.

The flops are the histogram kernel's own one-hot formulation, the one
``arith.gbdt_hist_mxu_flops`` counts for a level-wise tree: a built row
contracts its 4 operand rows (g and h, each a bf16 hi/lo pair) with the
[n_bins] one-hot of every feature. A program that reads every row of the
table for every node (70 passes a tree) does those flops for rows that
belong to no node it builds, and they do not count here; one that reads
only a node's rows is read against the same numbers."""

from __future__ import annotations


def grow_hist_mxu_flops(rows_built: float, n_features: int,
                        n_bins: int) -> float:
    """MXU flops of the one-hot histogram matmuls of the rows that had to
    be built: 2 * 4 * n_bins * n_features a row."""
    return 2.0 * 4 * n_bins * n_features * rows_built


def grow_hist_scanned_bytes(rows_built: float, n_features: int) -> float:
    """Least bytes those rows' histograms must read: a row's n_features
    bin bytes (256 bins fit a byte) and its g and h."""
    return float(rows_built * (n_features + 8))
