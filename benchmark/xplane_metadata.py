"""The one thing of an .xplane.pb that jax.profiler.ProfileData does not
show: the stats kept with an event's METADATA (one record for all events
of a name), where the TPU's profiler puts what it knows of an operation
from its HLO: `tf_op` (the op name with the jax.named_scope path it was
traced under), `hlo_category`, `source`, `flops`, `bytes_accessed`.
ProfileData's `event.stats` holds the event's own stats alone (offset and
duration on the device).

Read from the protobuf wire format directly (xplane.proto, tsl/profiler),
with nothing but the standard library; only the fields named below are
looked at, everything else is skipped by its length.

  XSpace.planes = 1
  XPlane.name = 2, .event_metadata = 4 (map), .stat_metadata = 5 (map)
  map entry: key = 1, value = 2
  XEventMetadata.name = 2, .stats = 5
  XStatMetadata.id = 1, .name = 2
  XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
        .str_value = 5, .ref_value = 7 (a string kept as a stat's name)
"""

from __future__ import annotations


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def fields(buf):
    """(field number, value) for each field of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", errors="replace")


def _map_values(entries):
    for entry in entries:
        for number, value in fields(entry):
            if number == 2:
                yield value


def event_metadata_stats(path: str) -> dict:
    """{plane name: {event name: {stat name: value}}}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in fields(space):
        if number != 1:
            continue
        name, events, stat_entries = "", [], []
        for number, value in fields(plane):
            if number == 2:
                name = _text(value)
            elif number == 4:
                events.append(value)
            elif number == 5:
                stat_entries.append(value)
        stat_names = {}
        for meta in _map_values(stat_entries):
            got = dict(fields(meta))
            stat_names[got.get(1, 0)] = _text(got.get(2, b""))
        per_event = out.setdefault(name, {})
        for meta in _map_values(events):
            event_name, stats = "", {}
            for number, value in fields(meta):
                if number == 2:
                    event_name = _text(value)
                elif number == 5:
                    got = dict(fields(value))
                    key = stat_names.get(got.get(1), got.get(1))
                    if 5 in got:
                        stats[key] = _text(got[5])
                    elif 7 in got:
                        stats[key] = stat_names.get(got[7], "")
                    else:
                        stats[key] = got.get(3, got.get(4))
            per_event[event_name] = stats
    return out
