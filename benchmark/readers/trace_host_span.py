"""The program's own host spans on the profiler's clock: over the host
events named exactly ``spec["span"]`` inside the traced window (cut to
it), ``spec["stat"]`` of their durations in seconds (``sum``, ``count``,
``median`` or ``max``), times ``spec["scale"]``, over the counter named
``spec["per"]``.

``count`` of a span that did not occur is 0; the other statistics then
have nothing to read. Where ``spec["instrumented"]`` is given and no host
event of the whole trace matches it, the program records no such spans at
all (the parent of the PR that added them) and there is nothing to read
either."""

import re
import statistics

STATS = {"sum": sum, "count": len, "median": statistics.median, "max": max}


def read(spec: dict, run: dict):
    trace = run.get("trace")
    per = run["counters"].get(spec["per"]) if "per" in spec else 1
    if trace is None or not per:
        return None
    if "instrumented" in spec and not len(
            trace.host.matching(spec["instrumented"])):
        return None
    t0, t1 = run["window_ns"]
    found = trace.host.matching(f"^{re.escape(spec['span'])}$").clip(t0, t1)
    stat = spec["stat"]
    if not len(found) and stat != "count":
        return None
    seconds = [float(v) / 1e9 for v in found.end - found.start]
    return STATS[stat](seconds) * spec.get("scale", 1.0) / per
