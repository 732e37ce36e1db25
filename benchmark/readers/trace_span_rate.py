"""What the program says it moved, over the time it took to move it: the
sum of the argument ``spec["argument"]`` (``bytes``) of the host events
named exactly ``spec["span"]`` that lie inside the traced window and hold
at least one event matching ``spec["child"]``, over the union of those
events' time, times ``spec["scale"]`` (1e-9: GB/s).

The child is a wait (``mp4j.stage.link_wait`` / ``device_wait``): a
``mp4j.put_sharded`` that holds none handed its array to the runtime and
returned, and its time says nothing about when the bytes landed. The
bytes are the program's own count of what it sent, not the benchmark's
arithmetic of the table, so the rate stays true when fewer bytes cross. A
rate, and no share of a peak: ``peaks.json`` has no host link.

A span's arguments are stats of its host event, which ``xplane.Events``
drop, so this reads the run's file itself, found as
``readers/trace_scope_time.py`` finds it (``run["trace_path"]``, else the
newest under ``benchmark/out/trace/``). A file whose events named
``spec["span"]`` are not those of ``run["trace"]`` is another run's.
Nothing to read, and nothing raised: no trace, no file, no event matching
``spec["instrumented"]`` (the parent of the PR that added the spans), no
such span that holds a wait (a table that crossed in one transfer), or no
such argument."""

import os
import re

import numpy as np

from benchmark import scopes, xplane


def _host_events(path: str):
    """(name, start, end, stats) of every event of the file's host plane."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    yield (e.name, e.start_ns, e.start_ns + e.duration_ns,
                           e.stats)


def read(spec: dict, run: dict):
    trace = run.get("trace")
    if trace is None or not len(trace.host.matching(spec["instrumented"])):
        return None
    path = run.get("trace_path") or scopes.newest_trace()
    if path is None or not os.path.isfile(path):
        return None
    child = re.compile(spec["child"])
    spans, waits = [], []
    for name, start, end, stats in _host_events(path):
        if name == spec["span"]:
            spans.append((start, end, dict(stats).get(spec["argument"])))
        elif child.search(name):
            waits.append((start, end))
    known = trace.host.matching(f"^{re.escape(spec['span'])}$")
    if sorted(s[0] for s in spans) != sorted(known.start.tolist()):
        return None
    t0, t1 = run["window_ns"]
    waited = [(start, end, moved) for start, end, moved in spans
              if t0 <= start and end <= t1 and moved is not None
              and any(start <= a and b <= end for a, b in waits)]
    if not waited:
        return None
    start, end, moved = (np.asarray(col, np.float64)
                         for col in zip(*sorted(waited)))
    took = xplane.union_ns(xplane.Events((spec["span"],) * len(waited),
                                         start, end))
    if took <= 0:
        return None
    return float(moved.sum()) / (took / 1e9) * spec.get("scale", 1.0)
