"""What a tree's histograms cost by the rows they *had* to be built
from, whatever the program read to build them, kept with the benchmark
like ``arith.py``. The root's histogram is built from all rows and, of
every split's two children, the smaller one's from its own rows (its
sibling's is the parent's less it); the needed rows are their sum,
counted by the benchmark from the returned trees and the host's table
(``reference/gbdt_rows_needed.py``) for a level-wise tree and a grown
one alike. The leaf-wise trainer's own counter
(``GBDTTrainer.grow_stats_["rows_built"]``) is held to the same count by
its cell's check.

The flops are the histogram kernel's own one-hot formulation: a needed
row contracts its 4 operand rows (g and h, each a bf16 hi/lo pair) with
the [n_bins] one-hot of every feature. A program that sends every row of
the table through the kernel at every level (32 N rows' worth of
one-node work a depth-6 tree), or reads the whole table for every node
of a grown tree, does those flops for rows that belong to no node it has
to build, and they do not count here; one that reads only a node's rows
is read against the same numbers."""

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
