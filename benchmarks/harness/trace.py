"""From the profiler's events to the numbers per-layer metrics read.

Everything is interval arithmetic on ``(start, end)`` pairs in seconds on
the profile's clock, which device planes and host threads share.  The
reduction keeps, per chip, the ops of the "XLA Ops" line (what ran on the
core) and of the "Async XLA Ops" line (copies and collectives in flight),
and from the host the spans the benchmark's own loop wrote
(``SPAN_PREFIX`` + kind).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

from . import xplane

Interval = Tuple[float, float]

SPAN_PREFIX = "bench_"
#: the loop's span kinds, innermost first: a gap that falls in several is
#: given to the first of these that covers most of it
SPAN_KINDS = ("epoch_turnover", "next_batch", "dispatch", "loss_fetch")
WINDOW_SPAN = "window"
#: a gap shorter than this sits between two ops of one program, not
#: between two programs: the core's own launch latency
BETWEEN_OPS_S = 50e-6


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the union ``a`` that the union ``b`` does not cover."""
    out: List[Interval] = []
    b = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, spans: Sequence[Interval]) -> float:
    return total(clip(spans, a))


def instruction(name: str) -> str:
    """``%fusion.7 = ... fusion(...)`` -> ``fusion.7``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def family(name: str) -> str:
    """``fusion.7`` -> ``fusion``: the instruction without its number."""
    return re.sub(r"[.\d]+$", "", instruction(name))


def scope_tail(tf_op: str) -> str:
    """``jit(f)/jvp(hvd_forward)/GPT/.../dot_general:`` ->
    ``hvd_forward/dot_general``: the benchmark-level scope and the
    primitive, which is how the breakdown names an op."""
    parts = [p for p in tf_op.rstrip(":").split("/") if p]
    if not parts:
        return ""
    scopes = [re.sub(r"^\w+\((.*)\)$", r"\1", p) for p in parts]
    scopes = [re.sub(r"^\w+\((.*)\)$", r"\1", s) for s in scopes]
    hvd = next((s for s in scopes if s.startswith("hvd_")), None)
    return f"{hvd}/{scopes[-1]}" if hvd else scopes[-1]


@dataclass
class Op:
    name: str          # HLO text of the instruction
    start: float
    end: float
    tf_op: str         # named_scope path, "" when the op has none

    @property
    def dur(self) -> float:
        return self.end - self.start


_ALLREDUCE = re.compile(r" all-reduce(-start|-done)?\(")


def is_allreduce(op: Op) -> bool:
    """By opcode, not by the instruction's name: the fused gradient
    all-reduce comes out of ``lax.psum`` as ``%psum.N = ... all-reduce(``
    and only a few are called ``%all-reduce.N``."""
    return _ALLREDUCE.search(op.name) is not None


def is_allreduce_done(op: Op) -> bool:
    return " all-reduce-done(" in op.name


def is_mosaic_kernel(op: Op) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op.name


@dataclass
class ChipTrace:
    ops: List[Op]        # "XLA Ops", clipped to the window
    async_ops: List[Op]  # "Async XLA Ops", clipped to the window

    def busy(self) -> List[Interval]:
        return union((o.start, o.end) for o in self.ops)

    def seconds(self, pred: Callable[[Op], bool]) -> float:
        return sum(o.dur for o in self.ops if pred(o))


@dataclass
class Reduced:
    window: Interval
    chips: List[ChipTrace]
    #: {span kind: intervals} from the thread that ran the loop
    spans: Dict[str, List[Interval]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(total(c.busy()) for c in self.chips) / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pred: Callable[[Op], bool]) -> float:
        """Summed durations of the ops ``pred`` picks, averaged over the
        chips."""
        return sum(c.seconds(pred) for c in self.chips) / len(self.chips)

    def allreduce_seconds(self) -> Tuple[float, float]:
        """(in flight, exposed), averaged over the chips: the union of the
        all-reduce ops' intervals on both lines, and the part of it during
        which no other op ran on that chip's core."""
        flight = exposed = 0.0
        for c in self.chips:
            ar = union((o.start, o.end) for o in c.ops + c.async_ops
                       if is_allreduce(o))
            other = [(o.start, o.end) for o in c.ops if not is_allreduce(o)]
            flight += total(ar)
            exposed += total(subtract(ar, other))
        n = len(self.chips)
        return flight / n, exposed / n

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The op families that took most device time, named
        ``<instruction family>_<scope>/<primitive>``, seconds averaged over
        the chips."""
        sums: Dict[str, float] = {}
        for c in self.chips:
            for o in c.ops:
                tail = scope_tail(o.tf_op)
                key = family(o.name) + (f"_{tail}" if tail else "")
                sums[key] = sums.get(key, 0.0) + o.dur
        n = len(self.chips)
        return sorted(((k_, v / n) for k_, v in sums.items()),
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds of chip 0 (the chips run one program in lockstep)
        by what the host's loop was doing meanwhile."""
        gaps = subtract([self.window], self.chips[0].busy())
        sums: Dict[str, float] = {}
        for gap in gaps:
            if gap[1] - gap[0] < BETWEEN_OPS_S:
                kind = "between_ops"
            else:
                kind, best = "other", 0.0
                for name in SPAN_KINDS:
                    cover = overlap(gap, self.spans.get(name, ()))
                    if cover > best * 1.0001:
                        kind, best = name, cover
                    if cover >= 0.5 * (gap[1] - gap[0]):
                        break
            sums[kind] = sums.get(kind, 0.0) + (gap[1] - gap[0])
        return sorted(sums.items(), key=lambda kv: -kv[1])[:k]


def _ops(events: Sequence[xplane.Event], window: Interval) -> List[Op]:
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start_s, lo), min(e.start_s + e.dur_s, hi)
        if t > s:
            out.append(Op(e.name, s, t, str(e.meta.get("tf_op", ""))))
    return out


def reduce(raw: xplane.RawTrace) -> Reduced:
    """Clip every chip's ops to the loop's ``window`` span and collect the
    loop's other spans."""
    host = raw.planes.get("/host:CPU", {})
    spans: Dict[str, List[Interval]] = {}
    for events in host.values():
        for e in events:
            if e.name.startswith(SPAN_PREFIX):
                spans.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                    (e.start_s, e.start_s + e.dur_s))
    if len(spans.get(WINDOW_SPAN, ())) != 1:
        raise RuntimeError(
            f"trace holds {len(spans.get(WINDOW_SPAN, ()))} "
            f"'{SPAN_PREFIX}{WINDOW_SPAN}' spans, want exactly one")
    window = spans.pop(WINDOW_SPAN)[0]
    chips = []
    for name in sorted(p for p in raw.planes if p.startswith("/device:TPU")):
        lines = raw.planes[name]
        chips.append(ChipTrace(_ops(lines.get("XLA Ops", ()), window),
                               _ops(lines.get("Async XLA Ops", ()), window)))
    if not chips:
        raise RuntimeError("trace holds no /device:TPU plane")
    if not any(c.ops for c in chips):
        raise RuntimeError("no op ran on the device inside the traced "
                           "window")
    return Reduced(window, chips, {k: union(v) for k, v in spans.items()})
