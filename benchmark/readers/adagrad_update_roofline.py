"""The AdaGrad step's table traffic as a share of its roofline, in
percent: the least time the chip could take to read and write the
distinct features' blocks of the traced chunks
(``arith_ffm.block_update_bytes`` over the HBM peak; no flops to speak
of) over the device time of everything the program ran under the scopes
matching ``spec["scope"]`` (``ffm.table_gather`` and ``ffm.table_update``:
the slots' gather, the distinct features' gather, the setting scatter).
It reads a few percent: the serial unit charges by the descriptor and
not by the byte, and the forward pass gathers a block a slot, not a
feature. A program that has no such scope, or an adapter that counts no
distinct features, gives nothing to read."""

from benchmark import arith_ffm
from benchmark.readers import trace_scope_time


def read(spec: dict, run: dict):
    chunks = run["counters"].get("chunks")
    distinct = run["counters"].get("distinct_features")
    if not chunks or not distinct:
        return None
    seconds = trace_scope_time.read({"scope": spec["scope"]}, run)
    if not seconds:
        return None
    c = run["config"]
    least_s = (arith_ffm.block_update_bytes(distinct, c["n_fields"], c["k"])
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * chunks / seconds
