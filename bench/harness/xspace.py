"""The profiler's ``.xplane.pb`` read with the stats ``ProfileData`` leaves
out: those the profiler keeps on an event's metadata, where a device's
"XLA Ops" line holds each HLO op's ``op_name`` metadata, the path of
``jax.named_scope`` names the op was traced under
(``jit(step)/admm_w/while/cond/dot_general:``).

``scoped(space)`` gives, per device, ``[scope_path, start_ns, dur_ns]`` for
each executed op that carries such a path, on the timeline ``trace.load``
uses (nanoseconds from the start of the profile); ``profile_start_ns``
gives that start on ``time.time_ns()``, the clock of the program's
recorded spans.  The schema below is the part of the profiler's XSpace
protocol buffer these need; protobuf skips the fields it does not name.
"""
from __future__ import annotations

import functools

from harness import trace

# the stat of an XLA op that holds its op_name metadata on TPU v5e traces
SCOPE_STAT = "tf_op"
OPS_LINE = "XLA Ops"

_FIELDS = {  # message: [(field, number, type or message, repeated)]
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, "int64", False), ("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False)],
}


@functools.cache
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _FIELDS.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if kind in _FIELDS:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xspace.{kind}"
            else:
                f.type = getattr(F, f"TYPE_{kind.upper()}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def read(path: str):
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def _stats(stats, names: dict) -> dict:
    """name -> value of each stat; ``names`` maps stat metadata ids to
    names, which also intern the strings a ``ref_value`` points at."""
    return {names.get(s.metadata_id, ""):
            names.get(s.ref_value, "") if s.ref_value
            else s.str_value or s.int64_value or s.uint64_value
            or s.double_value
            for s in stats}


def profile_start_ns(space) -> int | None:
    """The profile's start on ``time.time_ns()``, from the profiler's
    "Task Environment" plane."""
    for plane in space.planes:
        if plane.name == "Task Environment":
            names = {e.key: e.value.name for e in plane.stat_metadata}
            v = _stats(plane.stats, names).get("profile_start_time")
            return int(v) if v else None
    return None


def scoped(space, stat: str = SCOPE_STAT) -> dict:
    """Per device, ``[scope_path, start_ns, dur_ns]`` of each op of the
    "XLA Ops" line whose event or event metadata carries ``stat``."""
    out = {}
    for plane in space.planes:
        name = plane.name
        if not (name.startswith("/device:")
                and ":" in name[len("/device:"):]):
            continue
        dev = name.rsplit(":", 1)[-1]
        if not dev.isdigit():
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: _stats(e.value.stats, names).get(stat)
                for e in plane.event_metadata}
        rows = out.setdefault(dev, [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                path = _stats(e.stats, names).get(stat) or \
                    meta.get(e.metadata_id)
                if path:
                    rows.append([str(path),
                                 line.timestamp_ns + e.offset_ps / 1e3,
                                 e.duration_ps / 1e3])
    return out


def load(log_dir: str) -> dict:
    """``trace.load`` of the profile in ``log_dir``, with each device's
    ``scoped`` ops cut to the same window and the profile's start on the
    host clock as ``profile_start_ns``."""
    tr = trace.load(log_dir)
    space = read(trace._xplane(log_dir))
    t0, t1 = tr["window"]
    for dev, rows in scoped(space).items():
        if dev in tr["devices"]:
            tr["devices"][dev]["scoped"] = [
                r for r in rows if r[1] < t1 and r[1] + r[2] > t0]
    tr["profile_start_ns"] = profile_start_ns(space)
    return tr
