"""The bulk allreduce's share of its roofline, in percent: the bytes every
rank must send (``2 (n - 1) / n`` of the message, nccl-tests' bus
bandwidth) over the chip's published interconnect peak, over the device
time of the all-reduce operations of the bulk program."""

from benchmark import xplane


def read(spec: dict, run: dict):
    trace = run.get("trace")
    calls = run["counters"].get("bulk_allreduces")
    if trace is None or not calls:
        return None
    t0, t1 = run["window_ns"]
    seconds = xplane.op_seconds(trace, spec["pattern"], t0, t1,
                                module=spec.get("module"))
    if not seconds:
        return None
    n = run["config"]["ranks"]
    wire_bytes = 2.0 * (n - 1) / n * run["config"]["bulk_elements"] * 4
    least_s = wire_bytes / (run["peaks"]["ici_bits_per_s"] / 8.0)
    return 100.0 * least_s * calls / seconds
