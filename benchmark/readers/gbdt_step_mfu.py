"""The whole GBDT training job's share of the chip's bf16 peak, in
percent: the one-hot flops of the histograms the traced jobs' trees
needed (``hist_rows_needed``, the benchmark's own count over all the
slice's jobs, a chip's share of it, at ``arith_grow.grow_hist_mxu_flops``
a row: the flops ``hist_kernel_roofline`` counts; everything else a tree
computes is a thousandth of it) over the peak times the wall time of the
traced slice, so staging, a raw table's sketch and transform, the
kernel's passes over rows no node needed, routing, the split search, the
gaps between launches and the fetch all count as time.

It cannot pass the kernel's share of its roofline. Unlike that share it
needs no kernel in the trace, only the slice and the rows its trees
needed: a program that builds its histograms another way is read against
the same flops."""

from benchmark import arith_grow, step_mfu


def read(spec: dict, run: dict):
    rows = run["counters"].get("hist_rows_needed")
    if not rows:
        return None
    c = run["config"]
    flops = arith_grow.grow_hist_mxu_flops(rows / run["chips"],
                                           c["n_features"], c["n_bins"])
    return step_mfu.percent_of_peak(flops, run)
