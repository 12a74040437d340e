"""Read the profiler's ``.xplane.pb`` into plain Python.

``jax.profiler.ProfileData`` gives events and times but not the per-op
metadata the TPU runtime writes beside them (``tf_op``: the op's
``named_scope`` path), so the file is parsed here with ``google.protobuf``
against the XSpace schema (tsl/profiler/protobuf/xplane.proto), declared
below field for field.  No TensorFlow import.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_PACKAGE = "hvd_bench_xplane"


def _schema():
    f = descriptor_pb2.FileDescriptorProto()
    f.name = _PACKAGE + ".proto"
    f.package = _PACKAGE
    f.syntax = "proto3"

    def msg(name, *fields):
        m = f.message_type.add()
        m.name = name
        for fname, number, ftype, label, type_name in fields:
            fd = m.field.add()
            fd.name, fd.number, fd.type, fd.label = fname, number, ftype, label
            if type_name:
                fd.type_name = f".{_PACKAGE}.{type_name}"
        return m

    one, many = _T.LABEL_OPTIONAL, _T.LABEL_REPEATED
    msg("XStat", ("metadata_id", 1, _T.TYPE_INT64, one, None),
        ("double_value", 2, _T.TYPE_DOUBLE, one, None),
        ("uint64_value", 3, _T.TYPE_UINT64, one, None),
        ("int64_value", 4, _T.TYPE_INT64, one, None),
        ("str_value", 5, _T.TYPE_BYTES, one, None),
        ("bytes_value", 6, _T.TYPE_BYTES, one, None),
        ("ref_value", 7, _T.TYPE_UINT64, one, None))
    msg("XEvent", ("metadata_id", 1, _T.TYPE_INT64, one, None),
        ("offset_ps", 2, _T.TYPE_INT64, one, None),
        ("duration_ps", 3, _T.TYPE_INT64, one, None),
        ("stats", 4, _T.TYPE_MESSAGE, many, "XStat"))
    msg("XLine", ("id", 1, _T.TYPE_INT64, one, None),
        ("name", 2, _T.TYPE_STRING, one, None),
        ("timestamp_ns", 3, _T.TYPE_INT64, one, None),
        ("events", 4, _T.TYPE_MESSAGE, many, "XEvent"))
    msg("XEventMetadata", ("id", 1, _T.TYPE_INT64, one, None),
        ("name", 2, _T.TYPE_BYTES, one, None),
        ("stats", 5, _T.TYPE_MESSAGE, many, "XStat"))
    msg("XStatMetadata", ("id", 1, _T.TYPE_INT64, one, None),
        ("name", 2, _T.TYPE_STRING, one, None))
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = msg(entry, ("key", 1, _T.TYPE_INT64, one, None),
                ("value", 2, _T.TYPE_MESSAGE, one, value))
        m.options.map_entry = True
    msg("XPlane", ("id", 1, _T.TYPE_INT64, one, None),
        ("name", 2, _T.TYPE_STRING, one, None),
        ("lines", 3, _T.TYPE_MESSAGE, many, "XLine"),
        ("event_metadata", 4, _T.TYPE_MESSAGE, many, "EventMetadataEntry"),
        ("stat_metadata", 5, _T.TYPE_MESSAGE, many, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, _T.TYPE_MESSAGE, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


@dataclass
class Event:
    name: str          # an op's HLO text, or a host span's name
    start_s: float     # seconds from the start of the profile
    dur_s: float
    meta: Dict[str, object] = field(default_factory=dict)


@dataclass
class RawTrace:
    #: {plane name: {line name: [Event, ...]}}; lines of one name (host
    #: threads are all called "python" and the like) are concatenated
    planes: Dict[str, Dict[str, List[Event]]]


def _text(b) -> str:
    return b.decode("utf-8", "replace") if isinstance(b, bytes) else str(b)


#: per-op metadata the reduction reads; the rest (source stacks, layouts,
#: the compiler's own flops and bytes) is dropped while reading
KEPT_STATS = ("tf_op",)
#: lines of a device plane that are kept: what ran on the core, and the
#: copies and collectives in flight beside it
DEVICE_LINES = ("XLA Ops", "Async XLA Ops")


def read(path: str) -> RawTrace:
    """Parse one ``.xplane.pb``: of the device planes ``DEVICE_LINES``, of
    the host plane every line."""
    space = _schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in space.planes:
        is_device = plane.name.startswith("/device:TPU")
        if not (is_device or plane.name == "/host:CPU"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        metas = {}
        for key, em in plane.event_metadata.items():
            stats = {}
            for s in em.stats:
                sname = stat_names.get(s.metadata_id)
                if sname not in KEPT_STATS:
                    continue
                if s.str_value:
                    stats[sname] = _text(s.str_value)
                elif s.ref_value:
                    stats[sname] = stat_names.get(s.ref_value, "")
                else:
                    stats[sname] = (s.uint64_value or s.int64_value
                                    or s.double_value)
            metas[key] = (_text(em.name), stats)
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if is_device and line.name not in DEVICE_LINES:
                continue
            base = line.timestamp_ns * 1e-9
            out = lines.setdefault(line.name, [])
            for ev in line.events:
                name, stats = metas.get(ev.metadata_id, ("?", {}))
                out.append(Event(name, base + ev.offset_ps * 1e-12,
                                 ev.duration_ps * 1e-12, stats))
        planes[plane.name] = lines
    return RawTrace(planes)


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` run left under
    ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]
