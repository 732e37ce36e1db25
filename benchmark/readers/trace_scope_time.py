"""Device time of the operations the program ran under a named scope:
the ``XLA Ops`` events whose ``tf_op`` (the program's name stack, read by
``benchmark/scopes.py``) matches ``spec["scope"]`` anywhere in it and,
where given, whose printed text matches ``spec["pattern"]`` (inside runs
of programs matching ``spec["module"]``, where given), inside the traced
window; the union of their intervals, averaged over the chips that ran
anything; times ``spec["scale"]``, over the counter named ``spec["per"]``.

A scope entered inside a differentiated function comes out wrapped
(``jit(step)/transpose(jvp(ffm.x))/scatter-add:``), so the scope is
searched for and not anchored. A program that has the scope and ran
nothing under it in the window gives 0. No trace, no ``tf_op`` in it, or
a program that has no such scope at all (the parent of the PR that added
it) gives nothing to read."""

import re

import numpy as np

from benchmark import scopes, xplane


def _inside(hit, runs):
    """The events of ``hit`` that start inside one of ``runs`` (None or
    empty: none does)."""
    if not runs:
        return hit.take([])
    idx = np.searchsorted(runs.start, hit.start, side="right") - 1
    inside = (idx >= 0) & (hit.start < runs.end[np.maximum(idx, 0)])
    return hit.take(np.nonzero(inside)[0])


def read(spec: dict, run: dict):
    trace = run.get("trace")
    per = run["counters"].get(spec["per"]) if "per" in spec else 1
    if trace is None or not per:
        return None
    tf_ops = scopes.for_run(run)
    if tf_ops is None:
        return None
    scope = re.compile(spec["scope"])
    if not any(scope.search(tf_op) for names in tf_ops.values()
               for tf_op in names.values()):
        return None
    pattern = re.compile(spec.get("pattern", ""))
    t0, t1 = run["window_ns"]
    per_chip = []
    for chip, ev in trace.ops.items():
        ev = ev.clip(t0, t1)
        if not len(ev):
            continue
        wanted = {name for name, tf_op in tf_ops[chip].items()
                  if scope.search(tf_op) and pattern.search(name)}
        hit = ev.take([i for i, n in enumerate(ev.names) if n in wanted])
        if "module" in spec:
            runs = trace.modules.get(chip)
            hit = _inside(hit, runs and runs.matching(spec["module"]))
        per_chip.append(xplane.union_ns(hit) / 1e9)
    if not per_chip:
        return None
    return float(np.mean(per_chip)) * spec.get("scale", 1.0) / per
