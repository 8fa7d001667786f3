"""Device time by named scope, and the program's host spans, from a JAX
profiler trace (``.xplane.pb``).

* an operation's scope path is its HLO ``op_name``
  (``jit(allocate)/per_link/while/body/...``), which the trace keeps as
  the ``tf_op`` stat of the operation's event metadata; a scope is one
  ``/``-separated segment of it;
* the device time of a scope is the union of the intervals of the
  operations in it on a chip's ``XLA Ops`` line, clipped to the window and
  summed over the chips: a scoped ``while`` and the operations of its body
  (which the line nests inside it) are counted once;
* a host span's time is the sum of its events on the host plane, clipped
  to the window.

``jax.profiler.ProfileData`` does not expose event-metadata stats, so the
device planes are read here from the protobuf wire format of ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``).
"""
from __future__ import annotations

import math

from benchlib.tracing import HOST_PLANE, OPS_LINE

_OP_NAME_STAT = "tf_op"

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_ID, _MD_NAME, _MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """(field number, value) of each field of one message: an int for a
    varint, bytes for a length-delimited field, None for fixed widths."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i:i + size]
            i += size
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _line_events(b: bytes) -> tuple[int, list[tuple[int, int, int]]]:
    """The timestamp (ns) of one ``XLine`` and the metadata id, offset and
    duration (ps) of each of its events, read in place: a trace holds
    millions of events, too many to slice out one by one."""
    t0, events = 0, []
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            if key >> 3 == _LINE_TIMESTAMP_NS:
                t0 = v
            continue
        if wire != 2:
            i += 8 if wire == 1 else 4
            continue
        size, i = _varint(b, i)
        end = i + size
        if key >> 3 != _LINE_EVENTS:
            i = end
            continue
        mid = off = dur = 0
        while i < end:
            k = b[i]
            i += 1
            if k & 7 != 0 or k >= 0x80:     # a stat, or a field this skips
                i -= 1
                k, i = _varint(b, i)
                if k & 7 == 2:
                    size, i = _varint(b, i)
                    i += size
                elif k & 7 == 0:
                    _, i = _varint(b, i)
                else:
                    i += 8 if k & 7 == 1 else 4
                continue
            v = shift = 0
            while True:
                c = b[i]
                i += 1
                v |= (c & 0x7F) << shift
                if c < 0x80:
                    break
                shift += 7
            f = k >> 3
            if f == _EVENT_MD_ID:
                mid = v
            elif f == _EVENT_OFFSET_PS:
                off = v
            elif f == _EVENT_DURATION_PS:
                dur = v
        events.append((mid, off, dur))
    return t0, events


def _map_entry(b: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _op_names(plane_fields) -> dict[int, str]:
    """Event-metadata id -> the operation's ``op_name`` (without the
    ``:op_type`` suffix the trace appends), for the operations that carry
    one."""
    stat_names: dict[int, str] = {}
    metadata = []
    for f, v in plane_fields:
        if f == _PLANE_STAT_MD:
            k, md = _map_entry(v)
            stat_names[k] = next((x.decode() for g, x in _fields(md)
                                  if g == _MD_NAME), "")
        elif f == _PLANE_EVENT_MD:
            metadata.append(_map_entry(v)[1])
    out = {}
    for md in metadata:
        mid, op_name = 0, None
        for f, v in _fields(md):
            if f == _MD_ID:
                mid = v
            elif f == _MD_STATS:
                sid = value = None
                for g, x in _fields(v):
                    if g == _STAT_MD_ID:
                        sid = x
                    elif g == _STAT_STR:
                        value = x.decode()
                    elif g == _STAT_REF:
                        value = stat_names.get(x)
                if stat_names.get(sid) == _OP_NAME_STAT and value:
                    op_name = value
        if op_name is not None:
            out[mid] = op_name.rpartition(":")[0] or op_name
    return out


def _is_ops_line(b: bytes) -> bool:
    name = OPS_LINE.encode()
    for f, v in _fields(b):
        if f == _LINE_NAME:
            return v == name
        if f == _LINE_EVENTS:     # the name comes before the events
            return False
    return False


def op_intervals(data: bytes) -> dict[str, list[tuple[str, float, float]]]:
    """Every operation on the ``XLA Ops`` line of each device plane as
    ``(op_name, start_ns, end_ns)``; an operation with no ``op_name`` (a
    loop the compiler made, a copy it inserted) reads ``""``. Times are
    in whole ns on the trace's clock, as ``ProfileData`` gives them."""
    out = {}
    for f, plane in _fields(data):
        if f != _SPACE_PLANES:
            continue
        pf = list(_fields(plane))
        name = next((v.decode() for g, v in pf if g == _PLANE_NAME), "")
        if not name.startswith("/device:"):
            continue
        ops = None
        for g, line in pf:
            if g != _PLANE_LINES or not _is_ops_line(line):
                continue
            if ops is None:
                ops = []
                op_names = _op_names(pf)
            t0, events = _line_events(line)
            for mid, off, dur in events:
                s = float(t0 + off // 1000)       # whole ns, as ProfileData
                ops.append((op_names.get(mid, ""), s, s + dur // 1000))
        if ops is not None:
            out[name] = ops
    return out


def read_op_intervals(path: str) -> dict[str, list[tuple[str, float, float]]]:
    with open(path, "rb") as f:
        return op_intervals(f.read())


def scope_seconds(chips: dict, scope: str, lo: float = -math.inf,
                  hi: float = math.inf) -> float:
    """Device seconds of the operations in ``scope``: per chip the union
    of their intervals clipped to ``[lo, hi]`` (ns), summed over the
    chips."""
    total = 0.0
    for ops in chips.values():
        ivs = sorted((max(s, lo), min(e, hi)) for name, s, e in ops
                     if scope in name.split("/"))
        end = -math.inf
        for s, e in ivs:
            if e <= s:
                continue
            if s < end:
                if e > end:
                    total += e - end
                    end = e
            else:
                total += e - s
                end = e
    return total * 1e-9


def host_span_seconds(pd, name: str, lo: float = -math.inf,
                      hi: float = math.inf) -> tuple[float, int]:
    """Seconds and count of the host events called ``name`` that overlap
    ``[lo, hi]`` (ns), each clipped to it (``pd``: a ``ProfileData``)."""
    secs, n = 0.0, 0
    for pl in pd.planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name != name:
                    continue
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    secs += e - s
                    n += 1
    return secs * 1e-9, n
