"""Total time of the program's host spans named ``spec["span"]`` inside
the traced window, as ``trace_host_span`` sums them (times
``spec["scale"]``, over the counter named ``spec["per"]``), with one
difference: where the program is instrumented (a host event matches
``spec["instrumented"]``) and the span did not occur in the window, the
total is 0 and not nothing. For a span that marks a WAIT, absence is a
reading: the host never waited. ``trace_host_span`` leaves such a metric
out, which is right for a span whose absence means the program lacks it.
No trace, or a program that records no such spans at all (the parent of
the PR that added them), still gives nothing to read."""

from benchmark.readers import trace_host_span


def read(spec: dict, run: dict):
    value = trace_host_span.read({**spec, "stat": "sum"}, run)
    if value is not None:
        return value
    trace = run.get("trace")
    per = run["counters"].get(spec["per"]) if "per" in spec else 1
    if trace is None or not per or not len(
            trace.host.matching(spec["instrumented"])):
        return None
    return 0.0
