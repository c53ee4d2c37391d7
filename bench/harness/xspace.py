"""Read a JAX profiler capture (``*.xplane.pb``) with nothing but protobuf.

The message types are declared here with only the fields the benchmark
reads, under their wire numbers in ``tsl/profiler/protobuf/xplane.proto``
and ``xla/service/hlo.proto``; every other field is skipped by the parser.
Maps are declared as repeated ``(key, value)`` entries, which is the same
wire format.  Text fields are declared ``bytes`` so that no byte sequence
can fail a UTF-8 check.
"""
from __future__ import annotations

import functools
import gzip

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_MESSAGES = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "bytes", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, "int64", False), ("name", 2, "bytes", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "bytes", False),
                       ("display_name", 4, "bytes", False),
                       ("stats", 5, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "bytes", False),
              ("bytes_value", 6, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "bytes", False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("name", 1, "bytes", False),
                       ("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("name", 1, "bytes", False),
                            ("instructions", 2, "HloInstructionProto", True)],
    "HloInstructionProto": [("name", 1, "bytes", False),
                            ("opcode", 2, "bytes", False),
                            ("metadata", 7, "OpMetadata", False)],
    "OpMetadata": [("op_type", 1, "bytes", False),
                   ("op_name", 2, "bytes", False)],
}
_SCALARS = {"int64": _F.TYPE_INT64, "uint64": _F.TYPE_UINT64,
            "double": _F.TYPE_DOUBLE, "bytes": _F.TYPE_BYTES}


@functools.cache
def _classes() -> dict:
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="benchxs",
                                            syntax="proto2")
    for msg, fields in _MESSAGES.items():
        m = fd.message_type.add(name=msg)
        for name, num, typ, rep in fields:
            f = m.field.add(name=name, number=num,
                            label=(_F.LABEL_REPEATED if rep
                                   else _F.LABEL_OPTIONAL))
            if typ in _SCALARS:
                f.type = _SCALARS[typ]
            else:
                f.type = _F.TYPE_MESSAGE
                f.type_name = f".benchxs.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {m: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"benchxs.{m}")) for m in _MESSAGES}


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stat_value(s):
    for field in ("str_value", "bytes_value"):
        if s.HasField(field):
            v = getattr(s, field)
            return _text(v) if field == "str_value" else v
    for field in ("int64_value", "uint64_value", "double_value",
                  "ref_value"):
        if s.HasField(field):
            return getattr(s, field)
    return None


def read_xspace(path) -> list[dict]:
    """Planes of a capture (``.xplane.pb``, or gzipped) as plain data.

    Each plane: ``{"name", "stats", "lines": [{"name", "events": [...]}],
    "event_metadata": {id: {"name", "display_name", "stats"}}}``; each
    event ``{"metadata_id", "start_ns", "dur_ns", "stats"}``, its start on
    the capture's common clock (line timestamp plus offset).
    """
    cls = _classes()
    space = cls["XSpace"]()
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    for pl in space.planes:
        stat_names = {e.key: _text(e.value.name) for e in pl.stat_metadata}

        def stats(lst):
            return {stat_names.get(s.metadata_id, str(s.metadata_id)):
                    _stat_value(s) for s in lst}
        meta = {e.key: {"name": _text(e.value.name),
                        "display_name": _text(e.value.display_name),
                        "stats": stats(e.value.stats)}
                for e in pl.event_metadata}
        lines = []
        for ln in pl.lines:
            base = ln.timestamp_ns
            lines.append({"name": _text(ln.name), "events": [
                {"metadata_id": ev.metadata_id,
                 "start_ns": base + ev.offset_ps / 1e3,
                 "dur_ns": ev.duration_ps / 1e3,
                 "stats": stats(ev.stats) if ev.stats else {}}
                for ev in ln.events]})
        planes.append({"name": _text(pl.name), "stats": stats(pl.stats),
                       "lines": lines, "event_metadata": meta})
    return planes


def hlo_op_names(hlo_proto: bytes) -> dict:
    """``{instruction name: (opcode, op_name)}`` of one serialized
    ``HloProto``; ``op_name`` carries the ``jax.named_scope`` path."""
    cls = _classes()
    hp = cls["HloProto"]()
    hp.ParseFromString(hlo_proto)
    out = {}
    for comp in hp.hlo_module.computations:
        for ins in comp.instructions:
            out[_text(ins.name)] = (_text(ins.opcode),
                                    _text(ins.metadata.op_name))
    return out
