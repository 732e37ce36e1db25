"""Share of the traced window, in percent, in which no operation ran on
the device: 100 * (1 - busy / window), busy averaged over the chips."""

from benchmark import xplane


def read(spec: dict, run: dict):
    trace = run.get("trace")
    if trace is None:
        return None
    t0, t1 = run["window_ns"]
    busy = xplane.mean_busy_ns(trace, t0, t1)
    if t1 <= t0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (t1 - t0))
