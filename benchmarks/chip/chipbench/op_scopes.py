"""The HLO scope of each device op in a profiler trace: the ``tf_op``
stat that a TPU trace keeps in each op's event metadata (the op's HLO
``op_name``, which carries every ``jax.named_scope`` around it).

``jax.profiler.ProfileData`` gives each event's own stats but not its
metadata's, so this reads the ``.xplane.pb`` file's protobuf directly,
and only the part it needs: each device plane's event metadata, name and
``tf_op``.  ``scope_share`` prices a named scope, such as the program's
``weight_quant``, as a share of the slice's device busy time; ``prune``
cuts a trace down to what the readers use, for a small test file.
"""

from __future__ import annotations

import re

from chipbench import trace_reduce

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 3, 4, 5
LINE_NAME, LINE_EVENTS, EVENT_METADATA_ID = 2, 4, 1
MAP_KEY, MAP_VALUE = 1, 2
META_NAME, META_STATS = 2, 5
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7
STAT_META_ID, STAT_META_NAME = 1, 2
TF_OP = "tf_op"
HLO_HEAD = re.compile(rb"^%\S+ = (\(|[a-z0-9]+\[[0-9,]*\](\{[^}]*\})?)")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _wire_fields(buf: bytes):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, value


def _fields(buf: bytes):
    return ((field, value) for field, _, value in _wire_fields(buf))


def _encode_varint(value: int) -> bytes:
    out = bytearray()
    while True:
        b, value = value & 0x7F, value >> 7
        out.append(b | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _encode(field: int, wire: int, value) -> bytes:
    key = _encode_varint(field << 3 | wire)
    if wire == 0:
        return key + _encode_varint(value)
    if wire == 2:
        return key + _encode_varint(len(value)) + value
    return key + value


def _stat_names(plane: bytes) -> dict:
    """``{stat metadata id: stat name}`` of one plane."""
    names = {}
    for field, value in _fields(plane):
        if field == PLANE_STAT_METADATA:
            meta = dict(_fields(dict(_fields(value)).get(MAP_VALUE, b"")))
            names[meta.get(STAT_META_ID)] = meta.get(STAT_META_NAME, b"").decode()
    return names


def _plane_scopes(plane: bytes) -> tuple[str, dict]:
    name, stat_names, metas = "", _stat_names(plane), []
    for field, value in _fields(plane):
        if field == PLANE_NAME:
            name = value.decode()
        elif field == PLANE_EVENT_METADATA:
            metas.append(dict(_fields(value)).get(MAP_VALUE, b""))
    scopes = {}
    for entry in metas:
        event, op = "", ""
        for field, value in _fields(entry):
            if field == META_NAME:
                event = value.decode()
            elif field == META_STATS:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(STAT_METADATA_ID)) != TF_OP:
                    continue
                if STAT_STR in stat:
                    op = stat[STAT_STR].decode()
                elif STAT_REF in stat:          # an interned string
                    op = stat_names.get(stat[STAT_REF], "")
        if event:
            scopes[event] = op
    return name, scopes


def read_scopes(path: str) -> dict:
    """``{device op's event name: its tf_op}`` over the trace's TPU planes."""
    with open(path, "rb") as f:
        space = f.read()
    scopes: dict = {}
    for field, plane in _fields(space):
        if field != SPACE_PLANES:
            continue
        name, ops = _plane_scopes(plane)
        if trace_reduce.DEVICE_PLANE.match(name):
            scopes.update(ops)
    return scopes


def scope_share(reduced: dict, scopes: dict, scope: str):
    """Percent of the slice's device busy time in ops whose HLO op name
    holds ``scope`` (a ``trace_reduce`` reduction's events); ``None``
    where no op does."""
    spent = [s for name, s, _ in reduced["events"] if scope in scopes.get(name, "")]
    if not spent or reduced["busy_s"] <= 0:
        return None
    return 100.0 * sum(spent) / reduced["busy_s"]


def _short_name(name: bytes) -> bytes:
    """An HLO op's event name cut after its output type, or after the
    ``(`` that opens a tuple of them (``trace_reduce`` keys ops by no
    more); any other name whole."""
    m = HLO_HEAD.match(name)
    return m.group(0) if m else name


def _prune_plane(plane: bytes, keep_lines, keep_stats: set) -> bytes:
    """The plane with only the lines whose name ``keep_lines`` accepts,
    the event metadata their events use, HLO op names cut after their
    output type, and of the metadata's stats only those named in
    ``keep_stats``."""
    stat_names, used, lines = _stat_names(plane), set(), []
    for field, value in _fields(plane):
        if field == PLANE_LINES:
            line = dict(_fields(value))
            if keep_lines(line.get(LINE_NAME, b"").decode()):
                lines.append(value)
                used.update(dict(_fields(ev)).get(EVENT_METADATA_ID, 0)
                            for f, ev in _fields(value) if f == LINE_EVENTS)
    out = []
    for field, wire, value in _wire_fields(plane):
        if field == PLANE_LINES and value not in lines:
            continue
        if field == PLANE_EVENT_METADATA:
            entry = dict(_fields(value))
            if entry.get(MAP_KEY, 0) not in used:
                continue
            meta = b"".join(
                _encode(f, w, _short_name(v) if f == META_NAME else v)
                for f, w, v in _wire_fields(entry.get(MAP_VALUE, b""))
                if f != META_STATS
                or stat_names.get(dict(_fields(v)).get(STAT_METADATA_ID)) in keep_stats)
            value = _encode(MAP_KEY, 0, entry.get(MAP_KEY, 0)) + _encode(MAP_VALUE, 2, meta)
        out.append(_encode(field, wire, value))
    return b"".join(out)


def prune(space: bytes, keep_planes, keep_lines, keep_stats: set) -> bytes:
    """The trace with only the planes and lines whose names
    ``keep_planes`` and ``keep_lines`` accept, only the event metadata
    those lines use, HLO op names cut after their output type, and of the
    metadata's stats only those in ``keep_stats``: a small file for a test
    to read, which the reduction and ``read_scopes`` read as the whole."""
    out = []
    for field, wire, value in _wire_fields(space):
        if field == SPACE_PLANES:
            name = dict(_fields(value)).get(PLANE_NAME, b"").decode()
            if not keep_planes(name):
                continue
            value = _prune_plane(value, keep_lines, keep_stats)
        out.append(_encode(field, wire, value))
    return b"".join(out)
