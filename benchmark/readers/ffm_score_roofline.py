"""The FFM scoring program's share of its roofline, in percent: the
least time the chip could take for the traced jobs (the larger of
``arith_ffm_score.score_flops`` over the bf16 peak and
``arith_ffm_score.score_min_bytes`` over the HBM peak; the bytes bound it
at 39 slots of 157 parameters) over the device time of everything the
program ran under the scopes matching ``spec["scope"]``
(``ffm.table_gather`` and ``ffm.score.*``: the block gather, the select
and the pairs). It reads a few percent: the gather is charged by the
descriptor and not by the byte. A program that has no such scope (the
parent of the PR that added it) gives nothing to read."""

from benchmark import arith, arith_ffm_score
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
        arith_ffm_score.score_flops(rows, c["max_nnz"], c["k"]),
        arith_ffm_score.score_min_bytes(rows, c["max_nnz"], c["n_fields"],
                                        c["k"]),
        run["peaks"])
    return 100.0 * least_s * jobs / seconds
