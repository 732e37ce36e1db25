"""The AdaGrad FFM cell's arithmetic: the bytes one chunk's table update
needs, computed from shapes (``arith.py`` is left as it is; a PR that
adds a cell adds a file).

libffm's rule reads and writes, for every DISTINCT feature a chunk
holds, the feature's parameters (its ``n_fields`` vectors of ``k`` and
its linear weight) and an accumulator beside each. However the program
lays them out and however many descriptors its gathers and scatters
issue, those values have to come out of HBM once and go back once.
"""

from __future__ import annotations


def block_values(n_fields: int, k: int) -> int:
    """Values a feature owns under AdaGrad: parameters and accumulators."""
    return 2 * (n_fields * k + 1)


def block_update_bytes(distinct_features: float, n_fields: int,
                       k: int) -> float:
    """Least bytes one chunk's update moves through HBM: every distinct
    feature's f32 values read once and written once."""
    return 2.0 * distinct_features * block_values(n_fields, k) * 4
