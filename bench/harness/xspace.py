"""Read what ``ProfileData`` leaves out of a trace file: the stats of each
event's metadata.  A TPU trace keeps an operation's scope path there, in
the ``tf_op`` stat of the metadata its ``XLA Ops`` events point to, while
``ProfileData`` gives only the stats of the event itself.

A minimal reader of the protobuf wire format of ``XSpace``
(``tsl/profiler/protobuf/xplane.proto``) for the fields it needs:
``XSpace.planes`` (1); ``XPlane.name`` (2), ``event_metadata`` (4) and
``stat_metadata`` (5), maps whose entries hold a key (1) and a value (2);
``XEventMetadata.name`` (2) and ``stats`` (5); ``XStatMetadata.id`` (1)
and ``name`` (2); ``XStat.metadata_id`` (1) and its string value, inline
(``str_value``, 5) or interned (``ref_value``, 7: the name of a stat
metadata).  Lines and events are skipped by their length.
"""
from __future__ import annotations

import re

_LEN = 2


def _varint(b, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b, lo: int, hi: int):
    """(field number, wire type, value) of each field in ``b[lo:hi]``: an
    int for a varint, the (start, end) of a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == _LEN:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield key >> 3, wire, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(b, span):
    for f, wire, v in _fields(b, *span):
        if f == 2 and wire == _LEN:
            yield v


def event_stat(path: str, stat: str, planes: str) -> dict:
    """``{plane name: {event metadata name: value}}`` of the string stat
    ``stat`` of each event's metadata, on the planes whose name matches
    the regular expression ``planes``; ``""`` for a name whose metadata
    disagree."""
    with open(path, "rb") as f:
        b = memoryview(f.read())
    want = re.compile(planes)
    out = {}
    for f, wire, plane in _fields(b, 0, len(b)):
        if f != 1 or wire != _LEN:
            continue
        name, events, stat_names = None, [], {}
        for pf, pwire, v in _fields(b, *plane):
            if pwire != _LEN:
                continue
            if pf == 2:
                name = _text(b, v)
            elif pf == 4:
                events += _map_values(b, v)
            elif pf == 5:
                for sm in _map_values(b, v):
                    sid, sname = None, None
                    for sf, _, sv in _fields(b, *sm):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = _text(b, sv)
                    stat_names[sid] = sname
        if name is None or not want.search(name):
            continue
        ids = {k for k, v in stat_names.items() if v == stat}
        table = out.setdefault(name, {})
        for em in events:
            ev_name, value = None, None
            for mf, mwire, mv in _fields(b, *em):
                if mf == 2 and mwire == _LEN:
                    ev_name = _text(b, mv)
                elif mf == 5 and mwire == _LEN:
                    sid, sval = None, None
                    for sf, swire, sv in _fields(b, *mv):
                        if sf == 1:
                            sid = sv
                        elif sf == 5 and swire == _LEN:
                            sval = _text(b, sv)
                        elif sf == 7:
                            sval = stat_names.get(sv)
                    if sid in ids:
                        value = sval
            if ev_name is not None and value is not None:
                # a name two metadata share with two values says nothing
                table[ev_name] = (value if table.get(ev_name, value) == value
                                  else "")
    return out
