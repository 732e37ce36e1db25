"""The program's own names for device operations: from an ``.xplane.pb``
to ``{chip: {event name: tf_op}}``.

An ``XLA Ops`` event's name is the HLO instruction's printed text, which
carries no ``op_name``: a ``jax.named_scope`` does not appear in it. The
name stack is in the file all the same, as the stat ``tf_op`` on the
event's ``XEventMetadata`` (``jit(step)/gbdt.hist/pallas_call:``), and
``jax.profiler.ProfileData`` exposes an event's own stats only, not its
metadata's. So this reads the file itself: a decoder of the protobuf wire
format for exactly the fields it needs (tensorflow/tsl
``xplane.proto``), with no dependency, since neither ``tensorflow`` nor
``xprof`` may be on the machine with the chip.

    XSpace.planes = 1
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5
        (maps: entry.key = 1, entry.value = 2)
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
        (a ref_value is the id of the XStatMetadata whose name is the
        string)

``run`` (the dict a reader gets from ``run.py``) holds the ``Trace`` and
not the file's path, so ``newest_trace()`` finds the file: ``run.py``
removes and rewrites the cell's trace directory just before the slice, so
the newest ``.xplane.pb`` under ``benchmark/out/trace/`` is this run's.
``for_run`` checks that against the ``Trace``: a file that does not hold
the run's operations is not the run's.
"""

from __future__ import annotations

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
STAT = "tf_op"
_TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                       "trace", "*", "plugins", "profile", "*", "*.xplane.pb")
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of every field of one message:
    an int for a varint, a memoryview for a length-delimited field; fixed
    fields are skipped (none is needed)."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, pos = _varint(buf, pos)
            yield number, wire, value
        elif wire == _BYTES:
            size, pos = _varint(buf, pos)
            yield number, wire, buf[pos:pos + size]
            pos += size
        elif wire == _FIXED64:
            pos += 8
        elif wire == _FIXED32:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an "
                             f"xplane.pb, or a group, which it has none of")


def _field(buf, number: int):
    """Every value of field ``number`` in the message ``buf``."""
    return [v for n, _w, v in _fields(buf) if n == number]


def _entry(buf) -> tuple[int, memoryview]:
    """(key, value) of one entry of a ``map<int64, message>``."""
    key, value = 0, memoryview(b"")
    for n, _w, v in _fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _plane_tf_ops(plane) -> dict[str, str]:
    """{event metadata name: its ``tf_op``, or "" where it has none}. Two
    programs can print an instruction alike; such a name keeps every
    distinct ``tf_op``, joined by " | "."""
    stat_names, events = {}, []
    for n, _w, v in _fields(plane):
        if n == 5:
            key, meta = _entry(v)
            name = _field(meta, 2)
            stat_names[key] = _text(name[0]) if name else ""
        elif n == 4:
            events.append(_entry(v)[1])
    out: dict[str, str] = {}
    for meta in events:
        name, tf_op = "", ""
        for n, _w, v in _fields(meta):
            if n == 2:
                name = _text(v)
            elif n == 5:
                stat = {k: val for k, _w2, val in _fields(v)}
                if stat_names.get(stat.get(1)) != STAT:
                    continue
                if 5 in stat:
                    tf_op = _text(stat[5])
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        seen = out.get(name, "")
        if not tf_op or tf_op in seen.split(" | "):
            out[name] = seen
        else:
            out[name] = f"{seen} | {tf_op}" if seen else tf_op
    return out


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict[int, dict[str, str]]:
    """{chip: {event name: tf_op}} for the ``/device:TPU:<i>`` planes of
    the file (empty where it has none, as a CPU's trace)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _field(space, 1):
        name = _field(plane, 2)
        m = DEVICE_PLANE.match(_text(name[0])) if name else None
        if m:
            out[int(m.group(1))] = _plane_tf_ops(plane)
    return out


def newest_trace(pattern: str = _TRACES) -> str | None:
    found = glob.glob(pattern)
    return max(found, key=os.path.getmtime) if found else None


def for_run(run: dict) -> dict[int, dict[str, str]] | None:
    """The map for the traced run a reader was given: from
    ``run["trace_path"]`` where a caller put one, else from the newest
    trace under ``benchmark/out/trace/``. None where there is no file, no
    device plane, no ``tf_op`` in it, or where the file lacks an operation
    of ``run["trace"]`` (it is another run's)."""
    path = run.get("trace_path") or newest_trace()
    if path is None or not os.path.isfile(path):
        return None
    tf_ops = load(path)
    if not any(any(names.values()) for names in tf_ops.values()):
        return None
    for chip, events in run["trace"].ops.items():
        names = tf_ops.get(chip)
        if names is None or not set(events.names) <= names.keys():
            return None
    return tf_ops
