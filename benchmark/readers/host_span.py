"""Median host-clock duration of the spans named ``spec["span"]`` that
the benchmark recorded around a call into a layer, times
``spec["scale"]``."""

import statistics


def read(spec: dict, run: dict):
    seconds = run["spans"].get(spec["span"])
    if not seconds:
        return None
    return statistics.median(seconds) * spec.get("scale", 1.0)
