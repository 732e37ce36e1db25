"""The whole FFM slice's share of the chip's bf16 peak, in percent: the
model's own flops for the rows the slice consumed
(``arith_ffm_score.score_flops``: the pairs' dot products and the linear
term of one forward pass, times ``spec["passes"]``: 1 where the slice
scores, 3 where it trains, the backward pass costing about two forward
ones) on a chip's share of the rows, over the peak times the wall time
of the traced slice.

It reads thousandths of a percent: an FFM step is gathers, a sort and
scatters, charged by the descriptor, and its flops are next to nothing.
That is the reading: in the one unit every cell shares it says how far
from the MXU this work is, and it needs no scope in the trace."""

from benchmark import arith_ffm_score, step_mfu


def read(spec: dict, run: dict):
    rows = run["counters"].get("rows")
    if not rows:
        return None
    c = run["config"]
    flops = spec["passes"] * arith_ffm_score.score_flops(
        -(-rows // run["chips"]), c["max_nnz"], c["k"])
    return step_mfu.percent_of_peak(flops, run)
