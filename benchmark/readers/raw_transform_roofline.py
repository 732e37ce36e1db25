"""The quantile transform's share of its HBM roofline, in percent: the
least time the chip could take to read the traced jobs' float cells and
write their bins (``arith_raw.transform_least_bytes`` over the HBM peak)
over the device time of the operations under the program's scope
``spec["scope"]`` (``trace_scope_time``), which holds all of the
transform's device time.

The compare-count form does 254 compares and adds a cell, so it is bound
by the VPU and not by HBM: the share says how far the transform is from
the floor a form with fewer compares (or none) could reach, not how well
it uses the memory. Nothing to read where the program has no such scope
(the parent of the PR that added it) or ran nothing under it."""

from benchmark import arith_raw
from benchmark.readers import trace_scope_time


def read(spec: dict, run: dict):
    jobs = run["counters"].get("jobs")
    seconds = trace_scope_time.read({"scope": spec["scope"]}, run)
    if not jobs or not seconds:
        return None
    c = run["config"]
    least_s = (arith_raw.transform_least_bytes(
        -(-c["rows"] // run["chips"]), c["n_features"])
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * jobs / seconds
