"""The histogram kernel's share of its roofline in a tree grown leaf by
leaf, in percent: the least time the chip could take for the histograms
of the rows that had to be built from rows (``arith_grow``: the one-hot
flops over the bf16 peak or the rows' bytes over the HBM peak, whichever
is larger; the program's counter ``grow_rows_built`` says how many rows)
over the device time of the instructions that match ``spec["pattern"]``,
the kernel's calls. It counts the work and not the passes: while every
pass reads the whole table for one node's rows the share is low by about
the rows read over the rows built, and a program that reads only a
node's rows is read against the same yardstick. No counter (a program
without the policy), no trace or no kernel in it: nothing to read."""

from benchmark import arith, arith_grow, xplane


def read(spec: dict, run: dict):
    trace = run.get("trace")
    rows_built = run["counters"].get("grow_rows_built")
    if trace is None or not rows_built:
        return None
    t0, t1 = run["window_ns"]
    kernel_s = xplane.op_seconds(trace, spec["pattern"], t0, t1)
    if not kernel_s:
        return None
    c = run["config"]
    # the counter is the mesh's; a chip builds its share of the rows
    rows = rows_built / run["chips"]
    least_s, _bound = arith.roofline_seconds(
        arith_grow.grow_hist_mxu_flops(rows, c["n_features"], c["n_bins"]),
        arith_grow.grow_hist_scanned_bytes(rows, c["n_features"]),
        run["peaks"])
    return 100.0 * least_s / kernel_s
