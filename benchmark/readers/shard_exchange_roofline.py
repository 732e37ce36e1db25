"""The sharded FFM step's exchange as a share of its roofline, in percent:
the least time a chip's links could take to send what the traced chunks'
exchange must move (``arith_ffm_sharded.exchange_bytes_a_chip``: the
parameters of the distinct features a member touches and does not own,
out once and back once as gradients, over the chip's published
interconnect peak) over the device time of everything the program ran
under the scope ``spec["scope"]`` (``mp4j.all_to_all``). What the
implementation pads, caps or sends masked is its own and lowers the
share. A program that has no such scope, or an adapter that counts no
remote blocks, gives nothing to read."""

from benchmark import arith_ffm_sharded
from benchmark.readers import trace_scope_time


def read(spec: dict, run: dict):
    remote = run["counters"].get("remote_blocks")
    if not remote:
        return None
    seconds = trace_scope_time.read({"scope": spec["scope"]}, run)
    if not seconds:
        return None
    c = run["config"]
    least_s = (arith_ffm_sharded.exchange_bytes_a_chip(
        remote, run["chips"], c["n_fields"], c["k"])
        / (run["peaks"]["ici_bits_per_s"] / 8.0))
    return 100.0 * least_s / seconds
