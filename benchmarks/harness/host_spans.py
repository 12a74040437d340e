"""The program's own host spans, read from the profiler's ``.xplane.pb``
with their arguments.

``harness.xplane.read`` keeps a host event's name, start and duration and
drops the arguments the program gave it (``step_num``, the loader's
``epoch`` and ``batch``), which the profiler stores on the event and not on
its metadata.  This reader keeps them, and the line (the host thread) each
span was written on.  It reads what ``horovod_tpu.timeline.host_span``
writes: every span whose name starts with ``hvd_``.  No per-layer metric
reads it yet: ``harness.trace.reduce`` keeps only the loop's own
``bench_`` spans (PERF.md section 7 has the hand-over).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

from . import xplane

PROGRAM_PREFIX = "hvd_"


@dataclass
class HostSpan:
    name: str
    thread: str        # the line's name and id: one per host thread
    start_s: float     # seconds on the profile's clock
    dur_s: float
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s

    def inside(self, other: "HostSpan") -> bool:
        return other.start_s <= self.start_s and self.end_s <= other.end_s


def _value(stat, stat_names):
    if stat.str_value:
        return xplane._text(stat.str_value)
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return stat.int64_value or stat.uint64_value or stat.double_value or 0


def read(path: str, prefix: str = PROGRAM_PREFIX) -> List[HostSpan]:
    """Every host span of ``path`` whose name starts with ``prefix``, by
    start time."""
    space = xplane._schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    spans: List[HostSpan] = []
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        names = {k: xplane._text(v.name)
                 for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            base = line.timestamp_ns * 1e-9
            thread = f"{line.name}#{line.id}"
            for ev in line.events:
                name = names.get(ev.metadata_id, "?")
                if not name.startswith(prefix):
                    continue
                args = {stat_names.get(s.metadata_id, "?"):
                        _value(s, stat_names) for s in ev.stats}
                spans.append(HostSpan(
                    name, thread, base + ev.offset_ps * 1e-12,
                    ev.duration_ps * 1e-12, args))
    return sorted(spans, key=lambda s: s.start_s)
