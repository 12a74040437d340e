"""Tiles of rows a routed expert layer ran a step, mean over the layers:
how much the routers sent to the held experts, which is what the layer
costs (``parallel/moe.routed_experts`` takes each held expert's rows a tile
at a time, in a loop whose length the device decides; an even router gives
one tile an expert here).  Read from the device trace by counting: every
instruction of a loop's body runs once a tile, so each instruction under
``hvd_moe_experts`` ran (tiles of its layer) times a step, in the forward
loop and in the backward loop alike."""

import collections

from benchmarks.harness import qwen3_next_parts as parts
from benchmarks.harness import trace


def read(run):
    runs = collections.Counter(
        trace.instruction(op.name) for chip in run.reduced.chips
        for op in chip.ops if parts.MOE_EXPERTS in op.tf_op)
    if not runs:
        return None
    return sum(runs.values()) / len(runs) / run.steps / len(run.reduced.chips)
