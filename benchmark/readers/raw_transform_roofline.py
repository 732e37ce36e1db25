"""The quantile transform's share of its HBM roofline, in percent: the
least time the chip could take to read the traced jobs' float cells and
write their bins (``arith_raw.transform_least_bytes`` over the HBM peak)
over the device time of the operations under the program's scope
``spec["scope"]`` (``trace_scope_time``), which holds all of the
transform's device time.

Since PR 48 the transform is a Mosaic kernel (``mp4j_bin``: an
upper-bound search of a cell in its column's sorted edges, 8 steps at
254 edges) bound by the chip's cross-lane unit, which its lane gathers
keep busy, not by a VPU chain of compares and not by HBM: the share says
how far the transform is from the floor a form that only moved the bytes
could reach, not how well it uses the memory. In a scoring cell the scope
also holds each piece's slice out of the resident table. Nothing to read
where the program has no such scope (the parent of the PR that added it)
or ran nothing under it."""

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
