"""ISSUE 36's benchmark tests: the nine readers of the decoder layers' part
scopes and of the recompute's mark (``benchmarks/harness/part_scopes.py``),
on a hand-built trace with, of each part, a first run, a marked recompute
and a transposed op, a ``while`` envelope over marked body ops, and cells
with nothing to read; the entries in ``BENCHMARK.json`` by name.

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  Three accepted
tests pin the metric lists of ``kanana2-8k`` and ``sdar-bd4-8k`` to what
they were before this PR; ``tests/conftest.py`` marks them as expected
failures by name, and the last section here holds the same assertions with
this PR's entries in."""

import math

import pytest

import benchmark_tiny
from benchmarks.harness import part_scopes as parts
from benchmarks.harness import trace
from benchmarks.harness.spec import Spec
from benchmarks.run import RunRecord
from test_benchmark_kanana2 import NEW_READERS as PR_34_READERS
from test_benchmark_kanana2 import K2_STEP, _k2_run
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)
from test_benchmark_sdar import NEW_READERS as PR_30_READERS

THREE = ["qwen3next-8k", "sdar-bd4-8k", "kanana2-8k"]
SEVEN = ["gpt2s-1k", "gpt2s-16k", "gpt2s-1k-dp4", "qwen3next-8k",
         "sdar-bd4-8k", "gpt2s-4k", "kanana2-8k"]
#: {reader: the cells that list it}
CELLS = {
    "recompute_ms": THREE, "recompute_mixer_ms": THREE,
    "recompute_moe_ms": THREE,
    "attn_proj_ms": ["qwen3next-8k", "sdar-bd4-8k"],
    "gdn_proj_ms": ["qwen3next-8k"], "gdn_conv_ms": ["qwen3next-8k"],
    "mla_proj_ms": ["kanana2-8k"], "head_ms": SEVEN,
    "flash_layout_ms": SEVEN,
}
READERS = sorted(CELLS)

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


# -- the entries, by name -------------------------------------------------------


def test_the_nine_readers_are_entries_with_files_by_name():
    spec = Spec(benchmark_tiny.REPO)
    entries = {m["name"]: m for m in spec.data["per_layer"]}
    for name, cells in CELLS.items():
        entry = entries[name]
        assert entry["workloads"] == cells, name
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == ("ms", "lower", "device_trace", "mfu")
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    for name in ("recompute_ms", "recompute_mixer_ms", "recompute_moe_ms",
                 "head_ms"):
        assert entries[name]["layer"] == entries["bwd_ms"]["layer"]
    for name in ("gdn_proj_ms", "gdn_conv_ms"):
        assert entries[name]["layer"] == entries["gdn_ms"]["layer"]
    assert entries["mla_proj_ms"]["layer"] == entries["mla_ms"]["layer"]
    assert entries["flash_layout_ms"]["layer"] == entries["flash_ms"]["layer"]
    assert entries["attn_proj_ms"]["layer"] \
        == "mixers: models/qwen3_next and models/sdar softmax attention"
    # a cell lists a reader exactly where the table above says
    for cell in (w["name"] for w in spec.data["workloads"]):
        listed = set(spec.cell(cell).per_layer) & set(CELLS)
        assert listed == {n for n, cells in CELLS.items() if cell in cells}
    # nothing the benchmark had was edited: the entries before are the
    # accepted ones, in their order, ending on PR 34's seven
    before = [m["name"] for m in spec.data["per_layer"]
              if m["name"] not in CELLS]
    assert before[-7:] == PR_34_READERS
    assert len(before) == 47 and "resnet50-b256" not in {
        c for cells in CELLS.values() for c in cells}


# -- the accepted tests that pin the cells' lists, brought up to date ------------

ACCEPTED = {"init_s", "compile_s", "input_wait_ms", "dispatch_ms",
            "fwd_bwd_ms", "device_idle_pct", "hbm_gb", "fwd_ms", "bwd_ms",
            "flash_ms", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
            "grad_pack_ms", "unscoped_ms", "moe_ms", "moe_route_ms",
            "moe_tiles", "loss_ms"}


@pytest.mark.parametrize("cell,before", [
    ("kanana2-8k", PR_34_READERS), ("sdar-bd4-8k", PR_30_READERS)])
def test_what_a_cell_reports_after_pr_36(cell, before):
    mine = Spec(benchmark_tiny.REPO).cell(cell)
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert set(mine.per_layer) == ACCEPTED | set(before) | {
        n for n, cells in CELLS.items() if cell in cells}


def test_which_cells_list_which_metrics_after_pr_36():
    """PR 34's lists stand; this PR's nine follow them."""
    spec = Spec(benchmark_tiny.REPO)
    names = [m["name"] for m in spec.data["per_layer"]]
    assert names[-16:-9] == PR_34_READERS
    assert sorted(names[-9:]) == READERS
    entries = {m["name"]: m for m in spec.data["per_layer"]}
    for name in PR_34_READERS:
        assert entries[name]["workloads"] == ["kanana2-8k"]
    for name in ("fwd_ms", "bwd_ms", "unscoped_ms"):
        assert entries[name]["workloads"] == [
            "gpt2s-1k", "resnet50-b256", "gpt2s-16k", "gpt2s-1k-dp4",
            "qwen3next-8k", "sdar-bd4-8k", "gpt2s-4k", "kanana2-8k"]
    for name in ("flash_ms", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
                 "grad_pack_ms", "loss_ms"):
        assert entries[name]["workloads"] == SEVEN, name
