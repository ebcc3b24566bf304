"""Read a profiler's ``.xplane.pb`` whole: events *and* their metadata.

``jax.profiler.ProfileData`` gives planes, lines and events, but not the
statistics attached to an event's metadata, and on a TPU that is where an
operation's JAX name stack (``tf_op``: ``jit(grow_apply)/while/body/lgbm/
wave_partition/gather``) lives.  So the file is parsed as what it is, an
``XSpace`` protocol buffer (tsl/profiler/protobuf/xplane.proto), with the
message types declared here and nothing needed but ``google.protobuf``.
"""
from __future__ import annotations

_FIELDS = {
    # message: [(name, number, type, label, type_name)]
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
               ("lines", 3, "message", "repeated", "XLine"),
               ("event_metadata", 4, "message", "repeated", "EventMetaEntry"),
               ("stat_metadata", 5, "message", "repeated", "StatMetaEntry")],
    "EventMetaEntry": [("key", 1, "int64", "", ""),
                       ("value", 2, "message", "", "XEventMetadata")],
    "StatMetaEntry": [("key", 1, "int64", "", ""),
                      ("value", 2, "message", "", "XStatMetadata")],
    "XLine": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
              ("timestamp_ns", 3, "int64", "", ""),
              ("events", 4, "message", "repeated", "XEvent"),
              ("display_name", 11, "string", "", "")],
    "XEvent": [("metadata_id", 1, "int64", "", ""),
               ("offset_ps", 2, "int64", "", ""),
               ("duration_ps", 3, "int64", "", ""),
               ("stats", 4, "message", "repeated", "XStat")],
    "XStat": [("metadata_id", 1, "int64", "", ""),
              ("double_value", 2, "double", "", ""),
              ("uint64_value", 3, "uint64", "", ""),
              ("int64_value", 4, "int64", "", ""),
              ("str_value", 5, "string", "", ""),
              ("bytes_value", 6, "bytes", "", ""),
              ("ref_value", 7, "uint64", "", "")],
    "XEventMetadata": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
                       ("display_name", 4, "string", "", ""),
                       ("stats", 5, "message", "repeated", "XStat")],
    "XStatMetadata": [("id", 1, "int64", "", ""), ("name", 2, "string", "", "")],
}
_space_cls = None


def _xspace_class():
    global _space_cls
    if _space_cls is not None:
        return _space_cls
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    types = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
             "bytes": F.TYPE_BYTES, "message": F.TYPE_MESSAGE}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for mname, fields in _FIELDS.items():
        msg = fdp.message_type.add(name=mname)
        for fname, num, typ, label, tname in fields:
            fld = msg.field.add(name=fname, number=num, type=types[typ],
                                label=(F.LABEL_REPEATED if label == "repeated"
                                       else F.LABEL_OPTIONAL))
            if tname:
                fld.type_name = ".bench_xplane." + tname
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _space_cls = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _space_cls


def _stat_value(stat, stat_names: dict):
    for f in ("str_value", "int64_value", "uint64_value", "double_value"):
        v = getattr(stat, f)
        if v:
            return v
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    if stat.bytes_value:
        return stat.bytes_value.decode("utf-8", "replace")
    return 0


def read(path: str) -> list:
    """``[{"name", "lines": [{"name", "events": [(start_ns, dur_ns, name,
    meta_stats)]}]}]`` where ``meta_stats`` is the dict of the statistics of
    the event's metadata (shared by every occurrence of the operation)."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            m = e.value
            meta[e.key] = (m.name or m.display_name,
                           {stat_names.get(s.metadata_id, str(s.metadata_id)):
                            _stat_value(s, stat_names) for s in m.stats})
        lines = []
        for line in plane.lines:
            base = line.timestamp_ns
            evs = []
            for ev in line.events:
                name, mstats = meta.get(ev.metadata_id, ("", {}))
                evs.append((base + ev.offset_ps / 1e3, ev.duration_ps / 1e3,
                            name, mstats))
            lines.append({"name": line.name or line.display_name,
                          "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes
