"""ISSUE 36's benchmark tests: the nine readers of the decoder layers' part
scopes and of the recompute's mark (``benchmarks/harness/part_scopes.py``),
on a hand-built trace with, of each part, a first run, a marked recompute
and a transposed op, a ``while`` envelope over marked body ops, and cells
with nothing to read.

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  The nine
entries by name, and which cells list them, follow ``BENCHMARK.json`` in
``test_benchmark_lists.py`` (PR 40)."""

import math

import pytest

from benchmarks.harness import part_scopes as parts
from benchmarks.harness import trace
from benchmarks.run import RunRecord
from test_benchmark_kanana2 import K2_STEP, _k2_run
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

READERS = sorted([
    "recompute_ms", "recompute_mixer_ms", "recompute_moe_ms", "attn_proj_ms",
    "gdn_proj_ms", "gdn_conv_ms", "mla_proj_ms", "head_ms",
    "flash_layout_ms"])

# -- a hand-built step ----------------------------------------------------------

F = "jit(s)/jvp(hvd_forward)/Qwen3Next/"
T = "jit(s)/transpose(jvp(hvd_forward))/Qwen3Next/jvp(hvd_forward)/" \
    "Qwen3Next/checkpoint/"
R = T + "rematted_computation/"
GDN, ATTN = "layers_0/linear_attn/hvd_gdn/", "layers_1/self_attn/hvd_attn/"
MLA, MOE = "layers_2/self_attn/hvd_mla/", "layers_0/mlp/hvd_moe/"


def _fusion(i, path, start, end):
    return (f"%fusion.{i} = bf16[8] fusion(%p)", path, start, end)


#: one step of 100 ms: (HLO text, tf_op, start ms, end ms).  The forward
#: pass, then each layer's recompute and transposes.
STEP = [
    # first run: 0..30
    _fusion(1, F + GDN + "hvd_gdn_in/in_proj_qkvz/dot_general:", 0, 3),
    _fusion(2, F + GDN + "hvd_gdn_conv/add:", 3, 4),
    _fusion(3, F + GDN + "hvd_gdn_in/rsqrt:", 4, 4.5),
    ("%hvd_gdn_scan_fwd.4 = bf16[8]" + MOSAIC,
     F + GDN + "hvd_gdn_scan/hvd_gdn_scan_fwd/pallas_call:", 4.5, 8),
    _fusion(5, F + GDN + "hvd_gdn_out/out_proj/dot_general:", 8, 10),
    _fusion(6, F + ATTN + "hvd_attn_qkv/q_proj/dot_general:", 10, 12),
    _fusion(7, F + ATTN + "hvd_flash_layout/transpose:", 12, 12.5),
    ("%hvd_flash_fwd.8 = bf16[8]" + MOSAIC,
     F + ATTN + "jit(_fwd_call)/hvd_flash_fwd/hvd_flash_fwd/pallas_call:",
     12.5, 15),
    _fusion(9, F + ATTN + "hvd_attn_out/o_proj/dot_general:", 15, 16),
    _fusion(10, F + MLA + "hvd_mla_q/q_proj/dot_general:", 16, 18),
    _fusion(11, F + MLA + "hvd_mla_latent/kv_b_proj/dot_general:", 18, 19),
    _fusion(12, F + MLA + "hvd_mla_out/o_proj/dot_general:", 19, 20),
    _fusion(13, F + MOE + "hvd_moe_route/top_k:", 20, 21),
    ("%while.14 = (s32[]) while(%t)", F + MOE + "while:", 21, 24),
    _fusion(15, F + MOE + "while/body/hvd_moe_experts/dot_general:", 21, 24),
    _fusion(16, F + "layers_3/mlp/hvd_dense_mlp/dot_general:", 24, 25),
    _fusion(17, F + "hvd_head/dot_general:", 25, 29),
    _fusion(18, F[:-10] + "hvd_loss/reduce_sum:", 29, 30),
    # the head's transposes: 30..38
    _fusion(19, T[:-11] + "hvd_head/transpose:", 30, 38),
    # the recompute, marked: 40..56
    _fusion(20, R + GDN + "hvd_gdn_in/in_proj_qkvz/dot_general:", 40, 43),
    _fusion(21, R + GDN + "hvd_gdn_conv/add:", 43, 44),
    _fusion(22, R + GDN + "hvd_gdn_out/out_proj/dot_general:", 44, 46),
    _fusion(23, R + ATTN + "hvd_attn_qkv/q_proj/dot_general:", 46, 48),
    _fusion(24, R + ATTN + "hvd_flash_layout/transpose:", 48, 48.5),
    _fusion(25, R + ATTN + "hvd_attn_out/o_proj/dot_general:", 48.5, 49),
    _fusion(26, R + MLA + "hvd_mla_q/q_proj/dot_general:", 49, 51),
    _fusion(27, R + MLA + "hvd_mla_out/o_proj/dot_general:", 51, 52),
    # a loop of the recompute: its envelope and its body are one interval
    ("%while.28 = (s32[]) while(%t)", R + MOE + "while:", 52, 55),
    _fusion(29, R + MOE + "while/body/closed_call/hvd_moe_route/top_k:",
            52, 53.5),
    _fusion(30, R + MOE + "while/body/closed_call/hvd_moe_route/gather:",
            53.5, 55),
    _fusion(31, R + "layers_3/mlp/hvd_dense_mlp/dot_general:", 55, 56),
    # a layer's norm in the recompute: under no part
    _fusion(32, R + "layers_0/input_layernorm/rsqrt:", 56, 56.5),
    # transposed, unmarked: 60..90
    _fusion(33, T + GDN + "hvd_gdn_in/in_proj_qkvz/transpose:", 60, 66),
    _fusion(34, T + GDN + "hvd_gdn_conv/mul:", 66, 67),
    ("%hvd_gdn_scan_bwd.35 = bf16[8]" + MOSAIC,
     T + GDN + "hvd_gdn_scan/hvd_gdn_scan_bwd/pallas_call:", 67, 73),
    _fusion(36, T + GDN + "hvd_gdn_out/out_proj/transpose:", 73, 77),
    _fusion(37, T + ATTN + "hvd_attn_qkv/q_proj/transpose:", 77, 81),
    _fusion(38, T + ATTN + "hvd_flash_layout/reduce_sum:", 81, 82),
    _fusion(39, T + ATTN + "hvd_attn_out/o_proj/transpose:", 82, 84),
    _fusion(40, T + MLA + "hvd_mla_q/q_proj/transpose:", 84, 88),
    _fusion(41, T + MLA + "hvd_mla_out/o_proj/transpose:", 88, 90),
    _fusion(42, T + MOE + "while/body/hvd_moe_experts/dot_general:", 90, 96),
    ("%fusion.43 = f32[10] fusion(%p)", "jit(s)/hvd_optimizer_update/add:",
     96, 100),
]
WANT = {
    # every marked op: 40..56.5
    "recompute_ms": 16.5,
    # gdn 3 + 1 + 2, attention 2 + 0.5 + 0.5, latent attention 2 + 1
    "recompute_mixer_ms": 12.0,
    # the loop 3 (once), the dense layer 1
    "recompute_moe_ms": 4.0,
    # first 2 + 1, recomputed 2 + 0.5, transposed 4 + 2
    "attn_proj_ms": 11.5,
    # in 3 + 0.5, out 2; recomputed 3 + 2; transposed 6 + 4
    "gdn_proj_ms": 20.5,
    "gdn_conv_ms": 3.0,
    # q 2, out 1; recomputed 2 + 1; transposed 4 + 2
    "mla_proj_ms": 12.0,
    "head_ms": 12.0,
    "flash_layout_ms": 2.0,
}


def _parts_run(step=STEP) -> RunRecord:
    ops = [trace.Op(name, (100 * i + a) * MS, (100 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": {}, "mix": {}})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=100 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 100 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_reads_its_scopes_first_run_recompute_and_transposes(
        metric):
    assert math.isclose(_read(metric, _parts_run()), WANT[metric])


def test_the_parts_close_on_the_wholes_the_accepted_readers_read():
    run = _parts_run()
    # gdn: in / out, the convolution, the scan
    assert math.isclose(
        _read("gdn_proj_ms", run) + _read("gdn_conv_ms", run)
        + _read("gdn_scan_ms", run), _read("gdn_ms", run))
    assert math.isclose(_read("gdn_scan_ms", run), 3.5 + 6.0)
    # latent attention: q / out, the latent path (its kernels are not in
    # this step)
    assert math.isclose(
        _read("mla_proj_ms", run) + _read("mla_latent_ms", run),
        _read("mla_ms", run))
    # the recompute is a part of the backward pass, and its parts of it
    assert _read("recompute_mixer_ms", run) + _read("recompute_moe_ms", run) \
        <= _read("recompute_ms", run) < _read("bwd_ms", run)
    assert math.isclose(_read("bwd_ms", run), 8.0 + 16.5 + 3.0 + 36.0)
    # the marked loop is counted with the accepted expert readers too
    assert math.isclose(_read("moe_ms", run), 1.0 + 3.0 + 3.0 + 6.0)
    assert math.isclose(_read("loss_ms", run), 1.0)


def test_a_name_is_matched_whole_not_as_a_prefix():
    """``hvd_gdn_in`` does not answer for a longer name, nor ``hvd_head``
    for block diffusion's ``hvd_bd_head_rows``."""
    step = [
        _fusion(1, F + GDN + "hvd_gdn_inverses/mul:", 0, 1),
        _fusion(2, F + "hvd_bd_head_rows/slice:", 1, 2),
        _fusion(3, F + MLA + "hvd_mla_query/mul:", 2, 3),
        _fusion(4, "jit(s)/hvd_headroom/add:", 3, 4),
    ]
    run = _parts_run(step)
    for metric in READERS:
        assert _read(metric, run) is None, metric


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("step", ["gpt", "conv", "kanana2-before"])
def test_a_reader_reads_none_where_there_is_nothing_to_read(metric, step):
    """The parent of this PR (the accepted tests' hand-built steps carry no
    part scope and no mark) and cells of other models: nothing to read, no
    error, and the line leaves the metric out."""
    run = _k2_run(K2_STEP) if step == "kanana2-before" \
        else _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    if (metric, step) == ("mla_proj_ms", "kanana2-before"):
        # ``hvd_mla_q`` was there (PR 34), ``hvd_mla_out`` was not
        assert math.isclose(_read(metric, run), 1.0)
    else:
        assert _read(metric, run) is None


def test_the_mark_is_the_programs():
    from horovod_tpu.models import qwen3_next, scopes

    assert parts.REMAT_MARK == f"checkpoint/{qwen3_next.REMAT_MARK}/"
    mine = {parts.ATTN, parts.ATTN_QKV, parts.ATTN_OUT, parts.GDN,
            parts.GDN_IN, parts.GDN_CONV, parts.GDN_OUT, parts.MLA,
            parts.MLA_Q, parts.MLA_OUT, parts.MOE, parts.DENSE_MLP,
            parts.HEAD, parts.FLASH_LAYOUT}
    assert mine <= set(scopes.documented())


