"""Megabytes a step's all-reduces carry, per chip: the result shapes of the
ops whose opcode is ``all-reduce`` (or ``-start``), read from their HLO
text in the trace."""

import re

from benchmarks.harness import trace

_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def result_bytes(hlo: str) -> int:
    """Bytes of the shapes between ``=`` and the op's name."""
    head = hlo.split(" = ", 1)[1] if " = " in hlo else hlo
    head = head.split(" all-reduce", 1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def read(run):
    chip = run.reduced.chips[0]
    # a start/done pair names one transfer: count the op that produces it
    ops = [o for o in chip.ops if trace.is_allreduce(o)
           and not trace.is_allreduce_done(o)]
    if not ops:
        return None
    return sum(result_bytes(o.name) for o in ops) / run.steps / 1e6
