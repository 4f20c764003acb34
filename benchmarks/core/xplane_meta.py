"""What ``core/xplane.py load`` leaves in the trace file: the operations'
``op_name`` and the moment the profiler started.

``jax.profiler.ProfileData`` gives an event its name, start, duration and its
own stats (on a TPU: ``device_offset_ps``, ``device_duration_ps``). The HLO
metadata's ``op_name`` (``jit(fwd)/.../moe.route/jit(sort)/sort``: the path
of ``jax.named_scope`` names an operation was traced under) is a stat of the
event's *metadata*, ``tf_op``, which that API does not show, and the
profiler's start on the host's clock is a stat of the plane ``Task
Environment``. Both are read here from the ``.xplane.pb`` itself: it is a
protocol buffer (``XSpace``: planes; a plane: name 2, lines 3, event metadata
4, stat metadata 5, stats 6), and this walks its top level only, never the
lines, so a trace of a million events costs what its few thousand distinct
operations cost. The field numbers are those of ``xplane.proto`` (tsl), which
the profiler has kept since it was written.
"""

from __future__ import annotations

import os

from benchmarks.core import spec, xplane

OP_NAME_STAT = "tf_op"  # "<op_name>:<op type, empty on a TPU>"
START_PLANE = "Task Environment"
START_STAT = "profile_start_time"  # nanoseconds since the epoch


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message's top level: an int for a
    varint, a view of the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_values(entry):
    """The value of a map entry (key 1, value 2)."""
    for number, value in fields(entry):
        if number == 2:
            return value
    return b""


def _stats(message, stat_field: int, names: dict) -> dict:
    """``{stat name: value}`` of a message whose stats are ``stat_field``;
    a value is an int, a str, or a reference to a stat name."""
    out = {}
    for number, stat in fields(message):
        if number != stat_field:
            continue
        name = value = None
        for k, v in fields(stat):
            if k == 1:
                name = names.get(v)
            elif k in (3, 4):
                value = v
            elif k in (5, 6):
                value = _text(v)
            elif k == 7:
                value = names.get(v)
        if name is not None:
            out[name] = value
    return out


def read(path: str) -> dict:
    """``{"op_names": {plane: {event name: op_name}}, "start_s": seconds or
    None}`` of a trace file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    op_names, start_s = {}, None
    for number, plane in fields(space):
        if number != 1:
            continue
        name, metas, stat_names = "", [], {}
        for k, v in fields(plane):
            if k == 2:
                name = _text(v)
            elif k == 4:
                metas.append(_map_values(v))
            elif k == 5:
                doc = dict(fields(_map_values(v)))
                stat_names[doc.get(1, 0)] = _text(doc.get(2, b""))
        if name == START_PLANE:
            start = _stats(plane, 6, stat_names).get(START_STAT)
            start_s = start / 1e9 if isinstance(start, int) else start_s
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        table = op_names.setdefault(name, {})
        for meta in metas:
            event = next((_text(v) for k, v in fields(meta) if k == 2), None)
            op = _stats(meta, 5, stat_names).get(OP_NAME_STAT)
            if event is not None and isinstance(op, str):
                table[event] = op.rsplit(":", 1)[0]
    return {"op_names": op_names, "start_s": start_s}


def trace_path(run):
    return xplane.find_trace(os.path.join(
        spec.ROOT, "bench_out", "trace", run.cell["name"]))


def device_planes(run) -> list:
    """The device planes of the run's trace in ``core/xplane.py``'s tuple
    form, loaded once a run (``readers/trace_ops_time.py`` keeps them under
    the same attribute)."""
    planes = getattr(run, "_device_planes", None)
    if planes is None:
        path = trace_path(run) if run.trace else None
        planes = [(n, ls) for n, ls in (xplane.load(path) if path else [])
                  if n.startswith(xplane.DEVICE_PREFIX)]
        run._device_planes = planes
    return planes


def meta(run) -> dict:
    """``read`` of the run's trace, once a run; empty where there is no
    trace or its file cannot be walked."""
    found = getattr(run, "_trace_meta", None)
    if found is None:
        found = {"op_names": {}, "start_s": None}
        path = trace_path(run) if run.trace else None
        if path:
            try:
                found = read(path)
            except (OSError, ValueError, IndexError) as e:
                run.notes["trace_meta_error"] = repr(e)
        run._trace_meta = found
    return found
