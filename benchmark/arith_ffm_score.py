"""The FFM scoring cell's arithmetic: the operations and bytes one
scoring job needs, computed from shapes (``arith.py`` is left as it is;
a PR that adds a cell adds a file).

Whatever implements it, a job has to read, for every slot of every row,
the feature's live vectors against every field and its linear weight,
and the slot's id, field and value, and write a probability a row. Not
counted: the zeros a block is padded with, or the matmul an
implementation selects fields with; those are its own.
"""

from __future__ import annotations


def score_min_bytes(rows: int, nnz: int, n_fields: int, k: int) -> float:
    """Least bytes one job moves through HBM: ``n_fields * k + 1`` f32
    parameters and three 32-bit words a slot in, one f32 a row out."""
    return float(rows * nnz * ((n_fields * k + 1) * 4 + 12) + rows * 4)


def score_flops(rows: int, nnz: int, k: int) -> float:
    """The model's own flops a job: ``nnz (nnz - 1) / 2`` pairs of a
    k-long dot product times two values (2k + 2 each, the sum included),
    and the linear term's multiply and add a slot."""
    pairs = nnz * (nnz - 1) // 2
    return float(rows * (pairs * (2 * k + 2) + 2 * nnz))
