"""The histogram kernel's share of its roofline, in percent: the least
time the chip could take for the histograms the traced jobs' trees
needed, over the kernel's device time (the instructions that match
``spec["pattern"]``).

The work is counted by the benchmark and asks the program nothing:
``hist_rows_needed``, the rows the slice's jobs' trees had to build a
histogram from (``reference/gbdt_rows_needed.py``: all rows for a
tree's root and the smaller child's under every node whose children may
split, from the returned trees and the host's table; the leaf-wise cell
counts what its trainer builds, the children at the cap on depth too), a
chip taking its share of them. A needed
row costs the kernel's own one-hot formulation (``arith_grow``: 2 * 4 *
n_bins * n_features flops, 256 times what a scatter histogram adds up,
and the row's bin bytes with its g and h; the MXU bounds it at 256 bins).
So the share reads the same work whatever builds the histogram: a pass
over every row at every level, a slab of a child's own rows, the left
child or the smaller one. It says how much of the kernel's time went
into rows a tree needed, and on a level-wise tree's count, while a
histogram is a one-hot contraction, it cannot pass 100. No count, no
trace or no kernel in it: nothing to read."""

from benchmark import arith, arith_grow, xplane


def read(spec: dict, run: dict):
    trace = run.get("trace")
    rows = run["counters"].get("hist_rows_needed")
    if trace is None or not rows:
        return None
    t0, t1 = run["window_ns"]
    kernel_s = xplane.op_seconds(trace, spec["pattern"], t0, t1)
    if not kernel_s:
        return None
    c = run["config"]
    # the count is the mesh's; a chip builds its share of the rows
    rows = rows / run["chips"]
    least_s, _bound = arith.roofline_seconds(
        arith_grow.grow_hist_mxu_flops(rows, c["n_features"], c["n_bins"]),
        arith_grow.grow_hist_scanned_bytes(rows, c["n_features"]),
        run["peaks"])
    return 100.0 * least_s / kernel_s
