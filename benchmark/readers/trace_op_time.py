"""Device time of the operations whose printed text matches
``spec["pattern"]`` (inside runs of programs matching ``spec["module"]``,
where given), inside the traced window, averaged over the chips; times
``spec["scale"]``, over the counter named ``spec["per"]``. A trace in
which nothing matches gives 0: the operation did not run."""

from benchmark import xplane


def read(spec: dict, run: dict):
    trace = run.get("trace")
    if trace is None:
        return None
    t0, t1 = run["window_ns"]
    seconds = xplane.op_seconds(trace, spec["pattern"], t0, t1,
                                module=spec.get("module"))
    per = run["counters"].get(spec["per"]) if "per" in spec else 1
    if seconds is None or not per:
        return None
    return seconds * spec.get("scale", 1.0) / per
