"""The whole GBDT training job's share of the chip's bf16 peak, in
percent: the flops of the histogram matmuls of the traced trees
(``arith.gbdt_hist_mxu_flops``: the kernel's own one-hot formulation, the
flops ``hist_kernel_roofline`` counts; everything else a tree computes is
a thousandth of it) over the peak times the wall time of the traced
slice, so staging, a raw table's sketch and transform, routing, the
split search, the gaps between launches and the fetch all count as time.

It cannot pass the kernel's share of its roofline. Unlike that share it
needs no kernel in the trace, only the slice and the trees it finished:
a program that builds its histograms another way is read against the
same flops."""

from benchmark import arith, step_mfu


def read(spec: dict, run: dict):
    trees = run["counters"].get("trees")
    if not trees:
        return None
    c = run["config"]
    rows = -(-c["rows"] // run["chips"])
    flops = trees * arith.gbdt_hist_mxu_flops(rows, c["n_features"],
                                              c["n_bins"], c["depth"])
    return step_mfu.percent_of_peak(flops, run)
