"""The histogram kernel's share of its roofline, in percent: the least
time the chip could take for the traced trees (the larger of
``arith.gbdt_hist_mxu_flops`` over the bf16 peak and
``arith.gbdt_hist_scanned_bytes`` over the HBM peak; the MXU bounds it at
256 bins) over the kernel's device time. The flops are the kernel's own
one-hot formulation, 256 times what a scatter histogram adds up; the share
says how well the kernel feeds the MXU, and ``PERF.md`` says why it is
low (the one-hot is generated on the VPU)."""

from benchmark import arith, xplane


def read(spec: dict, run: dict):
    trace, trees = run.get("trace"), run["counters"].get("trees")
    if trace is None or not trees:
        return None
    t0, t1 = run["window_ns"]
    kernel_s = xplane.op_seconds(trace, spec["pattern"], t0, t1)
    if not kernel_s:
        return None
    c = run["config"]
    rows = -(-c["rows"] // run["chips"])
    least_s, _bound = arith.roofline_seconds(
        arith.gbdt_hist_mxu_flops(rows, c["n_features"], c["n_bins"],
                                  c["depth"]),
        arith.gbdt_hist_scanned_bytes(rows, c["n_features"], c["depth"]),
        run["peaks"])
    return 100.0 * least_s * trees / kernel_s
