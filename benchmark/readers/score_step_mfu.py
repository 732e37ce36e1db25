"""The whole GBDT scoring job's share of the chip's bf16 peak, in
percent: the flops of the traced jobs' column select
(``arith_score.score_select_flops``: the program's own one-hot
formulation, the flops ``score_roofline`` counts) over the peak times the
wall time of the traced slice, so the staging, a float table's binning,
the walk, the gaps between launches and the fetch all count as time.

It cannot pass the scoring program's share of its roofline. Unlike that
share it needs no scope in the trace, only the slice and the jobs it
finished."""

from benchmark import arith_score, step_mfu


def read(spec: dict, run: dict):
    jobs = run["counters"].get("jobs")
    if not jobs:
        return None
    c = run["config"]
    rows = -(-c["rows"] // run["chips"])
    flops = jobs * arith_score.score_select_flops(
        rows, c["n_features"], c["depth"], c["n_trees"])
    return step_mfu.percent_of_peak(flops, run)
