"""A count the run made (``spec["counter"]``), times ``spec["scale"]``."""


def read(spec: dict, run: dict):
    value = run["counters"].get(spec["counter"])
    if value is None:
        return None
    return value * spec.get("scale", 1.0)
