"""The numpy answer to a SUM allreduce followed by a division by the
number of ranks, on the exact small-integer operands of
``traffic.small_ints``."""

from __future__ import annotations

import numpy as np

from benchmark import traffic


def mean_of_ranks(index: np.ndarray, n_ranks: int, seed: int) -> np.ndarray:
    """What every rank holds after ``allreduce(SUM) / n_ranks`` (any
    number of times over: the mean of equal values is the value), at the
    flat element positions ``index`` of the message. Exact in f32: sums of
    integers below 8 over a few ranks, divided by a power of two."""
    index = np.asarray(index, np.uint32)
    total = np.zeros(index.shape, np.float64)
    for rank in range(n_ranks):
        total += traffic.small_ints(np, index, rank, seed)
    return (total / n_ranks).astype(np.float32)
