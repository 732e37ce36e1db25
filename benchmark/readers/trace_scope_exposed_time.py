"""Of the device time the program ran under a named scope
(``trace_scope_time``: the ``XLA Ops`` events whose ``tf_op`` matches
``spec["scope"]``, inside the traced window), the part in which NOTHING
ELSE ran on that chip: the union of the scope's intervals less what
other operations' intervals cover of it, averaged over the chips that ran
anything; times ``spec["scale"]``, over the counter named
``spec["per"]``. For a collective it is the exposed time: what the step
waits for, as against what it hides behind its own work.

An operation that only holds others (a ``while``, a ``conditional``, a
``call``: the ``XLA Ops`` line nests a loop's body inside the loop's
event) covers nothing by itself. Where the runtime issues a collective as
one synchronous operation and runs a chip's operations one after another,
all of its time is exposed and this reads what ``trace_scope_time`` reads;
it reads less where the collective is split into a start and a done with
work between them. No trace, no ``tf_op`` in it, or a program that has no
such scope gives nothing to read."""

import re

import numpy as np

from benchmark import scopes

_HOLDS_OTHERS = re.compile(r" (while|conditional|call)\(")


def _merged(start, end):
    """The union of intervals as sorted, disjoint ``(starts, ends)``."""
    if not len(start):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start)
    start, end = start[order], np.maximum.accumulate(end[order])
    opens = np.concatenate([[True], start[1:] > end[:-1]])
    first = np.nonzero(opens)[0]
    return start[first], end[np.concatenate([first[1:] - 1, [len(end) - 1]])]


def _covered(a, b):
    """Length of the part of the disjoint intervals ``a`` that the
    disjoint intervals ``b`` cover."""
    total = 0.0
    for s, e in zip(*a):
        lo = np.searchsorted(b[1], s, side="right")
        hi = np.searchsorted(b[0], e, side="left")
        if hi > lo:
            total += float(np.sum(np.minimum(b[1][lo:hi], e)
                                  - np.maximum(b[0][lo:hi], s)))
    return total


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
    t0, t1 = run["window_ns"]
    per_chip = []
    for chip, ev in trace.ops.items():
        ev = ev.clip(t0, t1)
        if not len(ev):
            continue
        under = np.asarray([bool(scope.search(tf_ops[chip].get(n, "")))
                            for n in ev.names])
        works = np.asarray([not _HOLDS_OTHERS.search(n) for n in ev.names])
        mine = _merged(ev.start[under], ev.end[under])
        others = _merged(ev.start[~under & works], ev.end[~under & works])
        alone = float(np.sum(mine[1] - mine[0])) - _covered(mine, others)
        per_chip.append(alone / 1e9)
    if not per_chip:
        return None
    return float(np.mean(per_chip)) * spec.get("scale", 1.0) / per
