"""``loss_ms`` (PR 27): the reader on hand-built ``Reduced`` objects — the
ops under ``hvd_loss`` forward and transposed, a loop counted once, not the
loss's all-reduce, ``None`` where a program has no such scope — and which
cells read it (its entry in ``BENCHMARK.json`` is held by name in
``test_benchmark_lists.py``)."""

import math

import pytest

import benchmark_tiny
from benchmarks.harness import trace
from benchmarks.harness.spec import Spec
from benchmarks.run import RunRecord
from test_benchmark_parts import (BWD, CFG, CONV_STEP, FWD, GPT_STEP, MIX,
                                  MS, PEAK, UNNAMED_STEP, _read, _run)

LOSS = FWD[:-4] + "hvd_loss/"
LOSS_T = BWD[:-4] + "hvd_loss/"
CELLS = ["gpt2s-1k", "gpt2s-16k", "gpt2s-1k-dp4", "qwen3next-8k"]


def test_loss_ms_reads_the_scope_and_not_the_losss_allreduce():
    # GPT_STEP: hvd_loss/reduce_sum 1.0 ms, hvd_loss_allreduce/psum 0.125
    assert math.isclose(_read("loss_ms", _run(GPT_STEP)), 1.0)


def test_loss_ms_reads_both_passes():
    step = GPT_STEP + [
        ("%fusion.20 = f32[8] fusion(%p)", LOSS_T + "mul:", 2.5)]
    run = _run(step)
    assert math.isclose(_read("loss_ms", run), 1.0 + 2.5)
    # the loss is inside hvd_forward: a part of fwd_bwd_ms, not beside it
    assert math.isclose(_read("fwd_bwd_ms", run),
                        _read("fwd_bwd_ms", _run(GPT_STEP)) + 2.5)
    assert math.isclose(_read("unscoped_ms", run),
                        _read("unscoped_ms", _run(GPT_STEP)))


def test_loss_ms_counts_a_loop_once():
    """The label pick's backward, as the parent compiles it at batch 1: a
    ``while`` that kept the scope lies over its body's ops."""
    ops = [
        trace.Op("%while.1 = (s32[]) while(%t)", 0.0, 10 * MS,
                 LOSS_T + "jit(take_along_axis)/scatter-add:"),
        trace.Op("%dynamic-update-slice.2 = f32[8] dynamic-update-slice(%a)",
                 1 * MS, 4 * MS, LOSS_T + "jit(take_along_axis)/scatter-add:"),
        trace.Op("%fusion.3 = f32[8] fusion(%a)", 12 * MS, 14 * MS,
                 LOSS + "reduce_max:"),
        trace.Op("%fusion.4 = bf16[8] fusion(%a)", 14 * MS, 20 * MS,
                 BWD + "wte.attend/dot_general:"),
    ]
    cell = type("Cell", (), {"cfg": CFG, "mix": MIX})
    run = RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=2, window_s=20 * MS,
                    reduced=trace.Reduced(
                        (0.0, 20 * MS), [trace.ChipTrace(ops, [])], {}))
    assert math.isclose(_read("loss_ms", run), (10 + 2) / 2)


@pytest.mark.parametrize("step", [CONV_STEP, UNNAMED_STEP],
                         ids=["no-loss-scope-conv", "no-loss-scope-gpt"])
def test_loss_ms_is_none_where_the_program_names_no_loss(step):
    assert _read("loss_ms", _run(step)) is None


@pytest.mark.parametrize("cell", CELLS + ["resnet50-b256"])
def test_which_cells_read_loss_ms(cell):
    per_layer = Spec(benchmark_tiny.REPO).cell(cell).per_layer
    if cell == "resnet50-b256":  # its loss is the adapter's own
        assert "loss_ms" not in per_layer
    else:
        assert hasattr(per_layer["loss_ms"], "read")
