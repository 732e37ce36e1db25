"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time by
operation, idle gaps and what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else. What the trace
of this installation looks like (TPU v5 lite, jax 0.9.0, libtpu 0.0.34;
checked against ``tests/data/*.xplane.pb``, recorded on the chip):

- one plane ``/device:TPU:<i>`` per chip; its line ``XLA Ops`` holds one
  event per executed HLO instruction, whose name is the instruction's
  text (``%step.6 = f32[...] custom-call(...), custom_call_target=...``);
  a ``while`` holds the events of its body, nothing else nests.
  ``XLA Modules`` holds one event per program run, named
  ``jit_<function>(<fingerprint>)``.
- the plane ``/host:CPU`` holds one line per host thread; ``python`` has
  the ``TraceAnnotation`` spans, the others the runtime's own events.
- host and device events are on one clock, nanoseconds from the start of
  the profile.

Busy time of a chip is the union of its ``XLA Ops`` events inside the
window; idle share is one minus busy over the window.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = .*?\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass(frozen=True)
class Events:
    """Events of one line (or several), sorted by start; times in ns."""
    names: tuple
    start: np.ndarray
    end: np.ndarray

    def __len__(self) -> int:
        return len(self.names)

    def take(self, keep) -> "Events":
        return Events(tuple(self.names[i] for i in keep),
                      self.start[keep], self.end[keep])

    def clip(self, t0: float, t1: float) -> "Events":
        """The events that overlap [t0, t1], cut to it."""
        ev = self.take(np.nonzero((self.end > t0) & (self.start < t1))[0])
        return Events(ev.names, np.maximum(ev.start, t0),
                      np.minimum(ev.end, t1))

    def matching(self, pattern: str) -> "Events":
        rx = re.compile(pattern)
        return self.take([i for i, n in enumerate(self.names)
                          if rx.search(n)])


def _events(lines) -> Events:
    names, start, dur = [], [], []
    for line in lines:
        for e in line.events:
            names.append(e.name)
            start.append(e.start_ns)
            dur.append(e.duration_ns)
    start = np.asarray(start, np.float64)
    dur = np.asarray(dur, np.float64)
    order = np.lexsort((-dur, start))       # a parent before its children
    start = start[order]
    return Events(tuple(names[i] for i in order), start, start + dur[order])


@dataclass(frozen=True)
class Trace:
    ops: dict        # chip index -> Events of its XLA Ops line
    modules: dict    # chip index -> Events of its XLA Modules line
    host: Events     # every event of every host thread


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, host_lines = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = _events([line])
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = _events([line])
        elif plane.name == HOST_PLANE:
            host_lines.extend(plane.lines)
    return Trace(ops, modules, _events(host_lines))


def union_ns(ev: Events) -> float:
    """Length of the union of the events' intervals."""
    total, reach = 0.0, -np.inf
    for s, e in zip(ev.start, ev.end):
        if e <= reach:
            continue
        total += e - max(s, reach)
        reach = e
    return total


def self_ns(ev: Events) -> np.ndarray:
    """Each event's duration less that of the events nested directly in
    it (a ``while`` holds the operations of its body on the same line)."""
    own = ev.end - ev.start
    open_ = []                              # indices of enclosing events
    for i in range(len(ev)):
        while open_ and ev.end[open_[-1]] <= ev.start[i]:
            open_.pop()
        if open_:
            parent = open_[-1]
            own[parent] -= min(ev.end[i], ev.end[parent]) - ev.start[i]
        open_.append(i)
    return own


def window_of(trace: Trace, span: str) -> tuple[float, float]:
    """[start, end] of the host span named ``span``; where the trace has
    none, from the first device operation to the last."""
    found = trace.host.matching(f"^{re.escape(span)}$")
    if len(found):
        return float(found.start[0]), float(found.end[0])
    starts = [ev.start[0] for ev in trace.ops.values() if len(ev)]
    ends = [ev.end.max() for ev in trace.ops.values() if len(ev)]
    if not starts:
        return 0.0, 0.0
    return float(min(starts)), float(max(ends))


def mean_busy_ns(trace: Trace, t0: float, t1: float) -> float:
    """Busy nanoseconds inside the window, averaged over the chips that
    ran anything in it."""
    busy = [union_ns(ev.clip(t0, t1)) for ev in trace.ops.values()]
    busy = [b for b in busy if b > 0]
    return float(np.mean(busy)) if busy else 0.0


def op_seconds(trace: Trace, pattern: str, t0: float, t1: float,
               module: str | None = None) -> float | None:
    """Seconds the operations whose text matches ``pattern`` ran inside
    the window (union, so nested matches count once), averaged over the
    chips that ran any operation in it; None where no chip ran any.
    ``module`` keeps only operations inside runs of programs whose name
    matches it."""
    per_chip = []
    for chip, ev in trace.ops.items():
        ev = ev.clip(t0, t1)
        if not len(ev):
            continue
        hit = ev.matching(pattern)
        if module is not None:
            runs = trace.modules.get(chip)
            runs = runs.matching(module) if runs is not None else None
            if runs is None or not len(runs):
                per_chip.append(0.0)
                continue
            idx = np.searchsorted(runs.start, hit.start, side="right") - 1
            inside = (idx >= 0) & (hit.start < runs.end[np.maximum(idx, 0)])
            hit = hit.take(np.nonzero(inside)[0])
        per_chip.append(union_ns(hit) / 1e9)
    return float(np.mean(per_chip)) if per_chip else None


def label(text: str) -> str:
    """A short, stable label for an HLO instruction's text: its name
    without the numeric suffix, and its opcode (with the target of a
    custom call)."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:80]
    name, opcode = m.groups()
    target = _TARGET.search(text)
    if target:
        opcode = f"{opcode} {target.group(1)}"
    return f"{name} ({opcode})"


def _first_chip(trace: Trace, t0: float, t1: float):
    for chip in sorted(trace.ops):
        ev = trace.ops[chip].clip(t0, t1)
        if len(ev):
            return ev
    return None


def top_ops(trace: Trace, t0: float, t1: float, limit: int = 10) -> list:
    """[[label, seconds], ...]: the device operations that took most
    time of their own inside the window, by label, averaged over the
    chips."""
    totals: dict[str, float] = {}
    chips = 0
    for ev in trace.ops.values():
        ev = ev.clip(t0, t1)
        if not len(ev):
            continue
        chips += 1
        for n, own in zip(ev.names, self_ns(ev)):
            key = label(n)
            totals[key] = totals.get(key, 0.0) + own
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9 / chips] for k, v in ranked]


def idle_gaps(trace: Trace, t0: float, t1: float, limit: int = 10,
              named: int = 256) -> list:
    """[[what the host was doing, idle seconds], ...] on the first chip
    that ran anything: the ``named`` longest gaps between device
    operations, each named by the innermost host event that covers most
    of it, summed by name; the rest as one entry."""
    ev = _first_chip(trace, t0, t1)
    if ev is None:
        return []
    gaps, reach = [], t0
    for s, e in zip(ev.start, ev.end):
        if s > reach:
            gaps.append((reach, s))
        reach = max(reach, e)
    if t1 > reach:
        gaps.append((reach, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = trace.host
    hdur = host.end - host.start
    totals: dict[str, float] = {}
    for a, b in gaps[:named]:
        name = "(no host event)"
        if len(host):
            overlap = np.minimum(host.end, b) - np.maximum(host.start, a)
            best = overlap.max()
            if best > 0:
                # most of the gap first, the shortest such event second
                cand = np.nonzero(overlap >= 0.5 * best)[0]
                name = host.names[cand[np.argmin(hdur[cand])]]
        totals[name] = totals.get(name, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit - 1]
    rest = sum(b - a for a, b in gaps[named:])
    out = [[k[:120], v / 1e9] for k, v in ranked]
    if rest > 0:
        out.append([f"({len(gaps) - named} shorter gaps)", rest / 1e9])
    return out


def breakdown(trace: Trace, t0: float, t1: float) -> dict:
    return {"device_ops": top_ops(trace, t0, t1),
            "idle_gaps": idle_gaps(trace, t0, t1)}
