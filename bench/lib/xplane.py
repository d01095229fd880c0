"""The scope path (`tf_op`) of each device op, read from the profiler's
`.xplane.pb` file itself.

`jax.profiler.ProfileData` gives an event's own stats but not those of its
event metadata, where a TPU plane keeps each op's `tf_op`: the
`jax.named_scope` path the program put it under, such as
`jit(tdp_lb_fused_one_run)/.../tdp.stage.fused/jit(run)/tdp.halo_extend/jit(_pad)/concatenate:`.
This reads the few fields of the XPlane schema (tsl/profiler/protobuf/xplane.proto)
that it needs straight from the protobuf wire format, so it depends on
nothing but Python.  Nothing in the benchmark reads it yet: its per-layer
metrics see op names only (`bench/lib/trace.py`).
"""
from __future__ import annotations

# Field numbers of the XPlane schema.
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 3, 4, 5
MAP_VALUE = 2
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_METADATA_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
EVENT_META_ID, EVENT_META_STATS = 1, 5
STAT_META_ID, STAT_META_NAME = 1, 2
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """Yield `(field_number, value)` for each top-level field of one
    message: an int for a varint, a memoryview for everything else."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", errors="replace")


def _map_values(entry):
    for f, v in fields(entry):
        if f == MAP_VALUE:
            return v
    return b""


def _stat_names(plane_fields) -> dict:
    names = {}
    for f, v in plane_fields:
        if f == PLANE_STAT_METADATA:
            meta = dict(fields(_map_values(v)))
            names[meta.get(STAT_META_ID, 0)] = _text(meta.get(STAT_META_NAME, b""))
    return names


def _op_paths(plane_fields, stat_names: dict) -> dict:
    """Event-metadata id -> the op's scope path: its `tf_op` stat without
    the trailing `:<type>`, or "" where it has none (an op the compiler
    made without a source location, such as a layout copy)."""
    paths = {}
    for f, v in plane_fields:
        if f != PLANE_EVENT_METADATA:
            continue
        mid, tf_op = 0, ""
        for mf, mv in fields(_map_values(v)):
            if mf == EVENT_META_ID:
                mid = mv
            elif mf == EVENT_META_STATS:
                stat = dict(fields(mv))
                if stat_names.get(stat.get(STAT_METADATA_ID)) != "tf_op":
                    continue
                if STAT_STR in stat:
                    tf_op = _text(stat[STAT_STR])
                elif STAT_REF in stat:
                    tf_op = stat_names.get(stat[STAT_REF], "")
        paths[mid] = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return paths


def op_scopes(data: bytes, line_name: str, keep) -> list:
    """`[(plane, [(path, start_ns, dur_ns)])]`: the events of the line
    `line_name` of every plane `keep(plane)` accepts, each with the scope
    path of its op, in the order the file holds them."""
    out = []
    for f, plane in fields(data):
        if f != SPACE_PLANES:
            continue
        name = next((_text(v) for pf, v in fields(plane) if pf == PLANE_NAME), "")
        if not keep(name):
            continue
        plane_fields = list(fields(plane))
        paths = _op_paths(plane_fields, _stat_names(plane_fields))
        for pf, line in plane_fields:
            if pf != PLANE_LINES:
                continue
            line_fields = list(fields(line))
            if next((_text(v) for lf, v in line_fields if lf == LINE_NAME), "") != line_name:
                continue
            t0_ps = 1000 * next((v for lf, v in line_fields if lf == LINE_TIMESTAMP_NS), 0)
            events = []
            for lf, ev in line_fields:
                if lf != LINE_EVENTS:
                    continue
                e = dict(fields(ev))
                events.append((paths.get(e.get(EVENT_METADATA_ID, 0), ""),
                               (t0_ps + e.get(EVENT_OFFSET_PS, 0)) // 1000,
                               e.get(EVENT_DURATION_PS, 0) // 1000))
            out.append((name, events))
    return out
