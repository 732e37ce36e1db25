"""Device time that none of the program's listed names covers: the
mirror of ``trace_scope_time.py``. Of the ``XLA Ops`` events inside the
traced window (inside runs of programs matching ``spec["module"]``, where
given), those whose ``tf_op`` (the program's name stack, read by
``benchmark/scopes.py``) matches ``spec["scope"]`` anywhere in it are
named; the reading is the busy time (the union of all the events'
intervals) less the union of the named ones', averaged over the chips
that ran anything; times ``spec["scale"]``, over the counter named
``spec["per"]``. So, with the same ``module``, the union of the listed
scopes and this add up to the busy time to the nanosecond.

A ``while`` holds the events of its body: a loop under a listed scope
names its whole body, and of a loop under none only the stretches count
in which nothing named ran. ``spec["scope"]`` lists the leaf scopes the
cell's other metrics read, as alternatives, and not a wrapper such as
``gbdt.level.<d>``, which would name all it wraps. A spec that lists
nothing reads the busy time. No trace, or no ``tf_op`` in it, gives
nothing to read; a program without the newest scopes (the parent of the
PR that added them) reads higher by what they name."""

import json
import re

import numpy as np

from benchmark import scopes, xplane
from benchmark.readers.trace_scope_time import _inside


def _split(spec: dict, run: dict):
    """[(events, named event names, {event name: tf_op})] a chip that ran
    anything in the window (and, with ``module``, in the matched runs);
    None where there is nothing to read."""
    trace = run.get("trace")
    if trace is None:
        return None
    tf_ops = scopes.for_run(run)
    if tf_ops is None:
        return None
    listed = re.compile(spec["scope"]) if spec.get("scope") else None
    t0, t1 = run["window_ns"]
    per_chip = []
    for chip, ev in trace.ops.items():
        ev = ev.clip(t0, t1)
        if "module" in spec:
            runs = trace.modules.get(chip)
            ev = _inside(ev, runs and runs.matching(spec["module"]))
        if not len(ev):
            continue
        named = set() if listed is None else {
            name for name, tf_op in tf_ops[chip].items()
            if listed.search(tf_op)}
        per_chip.append((ev, named, tf_ops[chip]))
    return per_chip or None


def longest(ev, named, tf_ops, limit: int = 5) -> list:
    """[[instruction, name stack, ns], ...]: what the residual of one chip
    is made of, by instruction (``xplane.label``) and ``tf_op``: each
    event's own time (less the events nested in it) where neither it nor
    a ``while`` around it is named. An empty name stack is an operation
    XLA made itself."""
    own = xplane.self_ns(ev)
    totals: dict[tuple, float] = {}
    open_ = []                  # (index, named or inside a named event)
    for i, name in enumerate(ev.names):
        while open_ and ev.end[open_[-1][0]] <= ev.start[i]:
            open_.pop()
        covered = name in named or bool(open_ and open_[-1][1])
        if not covered:
            key = (xplane.label(name), tf_ops.get(name, ""))
            totals[key] = totals.get(key, 0.0) + own[i]
        open_.append((i, covered))
    return [[*k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:limit]]


def read(spec: dict, run: dict):
    per = run["counters"].get(spec["per"]) if "per" in spec else 1
    if not per:
        return None
    per_chip = _split(spec, run)
    if per_chip is None:
        return None
    busy = [xplane.union_ns(ev) for ev, _, _ in per_chip]
    named = [xplane.union_ns(ev.take(
        [i for i, n in enumerate(ev.names) if n in names]))
        for ev, names, _ in per_chip]
    scale = spec.get("scale", 1.0) / 1e9 / per
    # one of the earlier lines of a run: what the residual holds, so that
    # the next scope is chosen from a traced run's own output
    print("unscoped: " + json.dumps({
        "metric": spec.get("name"), "busy": float(np.mean(busy)) * scale,
        "named": float(np.mean(named)) * scale,
        "longest": [[what, stack, ns * scale]
                    for what, stack, ns in longest(*per_chip[0])]}),
        flush=True)
    return float(np.mean(busy) - np.mean(named)) * scale
