"""The scoring program's share of its roofline, in percent: the least
time the chip could take for the traced jobs (the larger of
``arith_score.score_select_flops`` over the bf16 peak and
``arith_score.score_min_bytes`` over the HBM peak; the MXU bounds it at
968 columns and 500 trees) over the device time of everything the
program ran under the scopes matching ``spec["scope"]``
(``gbdt.score.*``: the select and the walk). The flops are the
program's own one-hot formulation of a column select, as the histogram
kernel's are; the share says how well the job feeds the MXU and how
little else it does. A program that has no such scope (the parent of
the PR that added it) gives nothing to read."""

from benchmark import arith, arith_score
from benchmark.readers import trace_scope_time


def read(spec: dict, run: dict):
    jobs = run["counters"].get("jobs")
    if not jobs:
        return None
    seconds = trace_scope_time.read({"scope": spec["scope"]}, run)
    if not seconds:
        return None
    c = run["config"]
    rows = -(-c["rows"] // run["chips"])
    least_s, _bound = arith.roofline_seconds(
        arith_score.score_select_flops(rows, c["n_features"], c["depth"],
                                       c["n_trees"]),
        arith_score.score_min_bytes(rows, c["n_features"]),
        run["peaks"])
    return 100.0 * least_s * jobs / seconds
