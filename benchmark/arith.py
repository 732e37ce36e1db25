"""The benchmark's arithmetic: peaks, and the operations and bytes a call
needs, computed from shapes. Kept here so that no PR that claims a gain
can change the yardstick. What a GBDT tree's histograms cost is in
``arith_grow.py``, by the rows a tree needs and not by the passes of one
implementation (until PR 57 ``gbdt_hist_mxu_flops`` here counted the
level-wise kernel's own operand, 32 N rows a depth-6 tree)."""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Published per-chip peaks of ``device_kind``. A device that is not
    in ``peaks.json`` is an error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    try:
        return table[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            f"to {_PEAKS_FILE} with its source") from None


def hist_level_nodes(depth: int) -> list[int]:
    """Nodes whose histograms one tree builds AND allreduces at each
    level: the root, then only the LEFT children (sibling subtraction,
    ``models/gbdt.py:_build_tree``): 1, 1, 2, 4, ... 2**(depth-2)."""
    return [1] + [2 ** (d - 1) for d in range(1, depth)]


def hist_message_bytes(depth: int, n_features: int, n_bins: int) -> list[int]:
    """Bytes of each level's histogram allreduce: gradient and hessian
    sums, f32, per (node, feature, bin)."""
    return [n * n_features * n_bins * 2 * 4 for n in hist_level_nodes(depth)]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "mxu") if t_flops >= t_bytes else (t_bytes, "hbm")


def ffm_rows_touched(rows: int, max_nnz: int) -> int:
    """Embedding-table rows one FFM chunk gathers, and scatters back:
    every slot pair (a, b) of every row touches v[feat_a, field_b]."""
    return rows * max_nnz * max_nnz


def busbw_bytes_per_s(n_ranks: int, message_bytes: float, seconds: float) -> float:
    """nccl-tests' bus bandwidth of an allreduce (doc/PERFORMANCE.md):
    algorithm bandwidth ``bytes / t`` times ``2 (n - 1) / n``, the share
    of the message every rank must both send and receive."""
    return 2.0 * (n_ranks - 1) / n_ranks * message_bytes / seconds
