"""The JAX profiler's ``.xplane.pb`` file, read with ``google.protobuf``.

The file is an ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``). Its
messages are declared here from that schema, with only the fields the
benchmark reads and their field numbers; the parser skips the rest.
``jax.profiler.ProfileData`` reads the same file but does not expose an
event's metadata stats, and the ``tf_op`` stat there is where an XLA
op's ``jax.named_scope`` path is kept.
"""

from __future__ import annotations

from typing import Dict

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _UINT64, _STRING = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_STRING
_PACKAGE = "bench_xplane"

#: message -> (field, number, type or message name, repeated)
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STRING, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    # the wire form of map<int64, XEventMetadata> and map<int64, XStatMetadata>
    "EventMetadataEntry": [("key", 1, _INT64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _INT64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, _STRING, False), ("timestamp_ns", 3, _INT64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _INT64, False),
               ("offset_ps", 2, _INT64, False),
               ("duration_ps", 3, _INT64, False)],
    "XStat": [("metadata_id", 1, _INT64, False),
              ("str_value", 5, _STRING, False),
              ("ref_value", 7, _UINT64, False)],
    "XEventMetadata": [("id", 1, _INT64, False), ("name", 2, _STRING, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, _INT64, False), ("name", 2, _STRING, False)],
}


def _xspace_class():
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number, label=(
                _F.LABEL_REPEATED if repeated else _F.LABEL_OPTIONAL))
            if isinstance(kind, str):
                f.type, f.type_name = _F.TYPE_MESSAGE, f".{_PACKAGE}.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read(path: str):
    """The ``XSpace`` message of the file at ``path``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_strings(plane, name: str) -> Dict[int, str]:
    """Event metadata id -> the string value of its stat ``name``, for
    every event metadata of ``plane`` that has one (a string held by
    reference is looked up in the plane's stat metadata)."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    want = {k for k, v in stat_names.items() if v == name}
    out: Dict[int, str] = {}
    for e in plane.event_metadata:
        for st in e.value.stats:
            if st.metadata_id in want:
                out[e.key] = st.str_value or stat_names.get(st.ref_value, "")
    return out
