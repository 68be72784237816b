"""Read a profiler trace (``.xplane.pb``) as the protobuf it is.

``jax.profiler.ProfileData`` gives planes, lines and events with their
times, and of an event's statistics only its own.  What says *which*
operation an event is sits one level up, in the plane's
``event_metadata``: for every operation of a TPU's ``XLA Ops`` line the
JAX name stack it was traced under (``tf_op``:
``jit(_step_impl)/layer/attn/dot_general:``; a ``jax.named_scope`` and
a ``pallas_call``'s ``name`` are components of it), the line of the
program's source it came from (``source``: ``/…/ops/sampling.py:57``),
its ``hlo_category``, ``flops``, ``bytes_accessed`` and the
``program_id`` of the jitted program it belongs to.  ``ProfileData``
does not show those, so this module parses the file itself.

It imports ``google.protobuf`` and nothing else: the message classes
are built here from the few fields of
``tsl/profiler/protobuf/xplane.proto`` that are read (a field not
listed is skipped by the parser), so neither tensorflow nor a profiler
plugin has to be importable where the reduction runs.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_INT64, _UINT64, _DOUBLE, _STRING, _MESSAGE = 3, 4, 1, 9, 11
_PACKAGE = "chipbench.xplane"

# message -> [(field number, name, type, repeated, message type)]: the
# fields this module reads, numbered as in xplane.proto.
_SCHEMA = {
    "XSpace": [(1, "planes", _MESSAGE, True, "XPlane")],
    "XPlane": [(2, "name", _STRING, False, None),
               (3, "lines", _MESSAGE, True, "XLine"),
               (4, "event_metadata", _MESSAGE, True, "EventMetadataEntry"),
               (5, "stat_metadata", _MESSAGE, True, "StatMetadataEntry")],
    # A proto3 map is a repeated entry of key 1 and value 2.
    "EventMetadataEntry": [(1, "key", _INT64, False, None),
                           (2, "value", _MESSAGE, False, "XEventMetadata")],
    "StatMetadataEntry": [(1, "key", _INT64, False, None),
                          (2, "value", _MESSAGE, False, "XStatMetadata")],
    "XLine": [(2, "name", _STRING, False, None),
              (3, "timestamp_ns", _INT64, False, None),
              (4, "events", _MESSAGE, True, "XEvent")],
    "XEvent": [(1, "metadata_id", _INT64, False, None),
               (2, "offset_ps", _INT64, False, None),
               (3, "duration_ps", _INT64, False, None)],
    "XStat": [(1, "metadata_id", _INT64, False, None),
              (2, "double_value", _DOUBLE, False, None),
              (3, "uint64_value", _UINT64, False, None),
              (4, "int64_value", _INT64, False, None),
              (5, "str_value", _STRING, False, None),
              (7, "ref_value", _UINT64, False, None)],
    "XEventMetadata": [(2, "name", _STRING, False, None),
                       (5, "stats", _MESSAGE, True, "XStat")],
    "XStatMetadata": [(2, "name", _STRING, False, None)],
}

# Where an XStat keeps its value, in the order to look.
_VALUE_FIELDS = ("str_value", "int64_value", "uint64_value", "double_value",
                 "ref_value")


def _space_class():
    file = descriptor_pb2.FileDescriptorProto(
        name="chipbench/xplane.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = file.message_type.add(name=message)
        for number, name, kind, repeated, message_type in fields:
            f = m.field.add(name=name, number=number, type=kind,
                            label=3 if repeated else 1)
            if message_type:
                f.type_name = f".{_PACKAGE}.{message_type}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


_XSPACE = _space_class()


def read_space(path: str):
    """The trace file as an ``XSpace`` message."""
    space = _XSPACE()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_values(stats, stat_names: dict) -> dict:
    """{statistic's name: value} of a repeated ``XStat``; a value that
    refers to a statistic's name (``ref_value``) is given as that
    name."""
    out = {}
    for stat in stats:
        name = stat_names.get(stat.metadata_id)
        if name is None:
            continue
        for field in _VALUE_FIELDS:
            value = getattr(stat, field)
            if value:
                out[name] = (stat_names.get(value, value)
                             if field == "ref_value" else value)
                break
        else:
            out[name] = 0
    return out


def event_metadata(plane) -> dict:
    """{metadata id: {"name", and every statistic the metadata carries
    by its name}} of a plane."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    return {e.key: {"name": e.value.name,
                    **stat_values(e.value.stats, stat_names)}
            for e in plane.event_metadata}


def line_events(line):
    """(metadata id, start_ns, duration_ns) of a line's events, the
    times as ``jax.profiler.ProfileData`` gives them: whole nanoseconds
    from picoseconds, the start counted from the line's own
    timestamp."""
    base = line.timestamp_ns
    for event in line.events:
        yield (event.metadata_id, int(base + event.offset_ps / 1000.0),
               int(event.duration_ps / 1000.0))
