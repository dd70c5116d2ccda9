"""The op path of every device operation in a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` (what ``trace.py`` reads) exposes the stats of
events, but not those of the event metadata, and on a TPU the op's path
(``jit(step)/transpose(jvp(layers))/.../attention/dot_general``) is the
``tf_op`` stat of the metadata. This reads the wire format of the
``XSpace`` protocol buffer with nothing but Python: the planes, their names,
their event and stat metadata; every other field, the events' lines among
them, is skipped by its length.

``tf_ops(path)`` gives, per device plane (``/device:...``), the map from an
event metadata's name (the text ``trace.load`` gives an operation) to its
``tf_op`` stat as written (``<path>:<type>``, the type empty for XLA's
ops). Two modules that hold the same instruction text keep the stat of the
last one read.
"""

from __future__ import annotations

# field numbers of tsl/profiler/protobuf/xplane.proto
XSPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVENT_META_NAME, EVENT_META_STATS = 2, 5
STAT_META_NAME = 2
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7
TF_OP = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field of the message in ``buf[lo:hi]``;
    the value of a length-delimited field is its (start, end) in ``buf``,
    that of a varint its integer, and fixed-width ones are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield num, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """(key, value span) of one map entry."""
    key, value = 0, (span[0], span[0])
    for num, v in fields(buf, *span):
        if num == MAP_KEY:
            key = v
        elif num == MAP_VALUE:
            value = v
    return key, value


def _plane(buf, span) -> tuple[str, dict[str, str]]:
    name, events, stat_names = "", [], {}
    for num, v in fields(buf, *span):
        if num == PLANE_NAME:
            name = _text(buf, v)
        elif num == PLANE_EVENT_METADATA:
            events.append(_map_values(buf, v)[1])
        elif num == PLANE_STAT_METADATA:
            key, meta = _map_values(buf, v)
            stat_names[key] = next((_text(buf, s) for n, s in fields(buf, *meta)
                                    if n == STAT_META_NAME), "")
    if not name.startswith("/device:"):
        return name, {}
    tf_op_ids = {k for k, n in stat_names.items() if n == TF_OP}
    ops = {}
    for meta in events:
        op, path = None, None
        for num, v in fields(buf, *meta):
            if num == EVENT_META_NAME:
                op = _text(buf, v)
            elif num == EVENT_META_STATS:
                path = _tf_op(buf, v, tf_op_ids, stat_names) or path
        if op is not None and path is not None:
            ops[op] = path
    return name, ops


def _tf_op(buf, span, tf_op_ids, stat_names) -> str | None:
    meta_id, value = None, None
    for num, v in fields(buf, *span):
        if num == STAT_METADATA_ID:
            meta_id = v
        elif num == STAT_STR:
            value = _text(buf, v)
        elif num == STAT_REF:
            value = stat_names.get(v)
    return value if meta_id in tf_op_ids else None


def tf_ops(path: str) -> dict[str, dict[str, str]]:
    """{device plane name: {event metadata name: tf_op}}."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, v in fields(buf):
        if num == XSPACE_PLANES:
            name, ops = _plane(buf, v)
            if name.startswith("/device:"):
                out[name] = ops
    return out
