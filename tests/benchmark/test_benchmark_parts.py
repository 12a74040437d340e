"""The per-layer metrics that read the program's nested scopes (forward /
backward, the three flash kernels, bucket packing, the update, what no
block owns), each on hand-built ``Reduced`` objects: every number against a
hand count, the parts against the wholes the accepted metrics read, and
``None`` where a cell has no such ops or the program does not name them."""

import importlib
import math

import pytest

from benchmarks.harness import flash_parts, flops, peaks, trace
from benchmarks.run import RunRecord

MS = 1e-3
STEPS = 2
PEAK = peaks.PEAKS["TPU v5 lite"]
CFG = {"n_layer": 12, "n_embd": 768, "n_head": 12, "n_inner": 3072,
       "vocab_size": 50257, "n_positions": 1024}
MIX = {"rows_per_chip": 8, "arrays": [{"shape": [1024]}]}

MOSAIC = ' custom-call(%q), custom_call_target="tpu_custom_call"'
FWD, BWD = "jit(s)/jvp(hvd_forward)/GPT/", \
    "jit(s)/transpose(jvp(hvd_forward))/GPT/"
#: one step of a GPT cell: (HLO text, tf_op, milliseconds)
GPT_STEP = [
    ("%fusion.1 = bf16[8] fusion(%p)", FWD + "dot_general:", 4.0),
    ("%fusion.2 = f32[] fusion(%p)", FWD[:-4] + "hvd_loss/reduce_sum:", 1.0),
    ("%hvd_flash_fwd.3 = f32[8]" + MOSAIC,
     FWD + "hvd_flash_fwd/hvd_flash_fwd/pallas_call:", 2.0),
    ("%fusion.4 = bf16[8] fusion(%p)", BWD + "dot_general:", 6.0),
    ("%hvd_flash_dq.5 = f32[8]" + MOSAIC,
     BWD + "hvd_flash_dq/hvd_flash_dq/pallas_call:", 3.0),
    ("%hvd_flash_dkv.6 = f32[8]" + MOSAIC,
     BWD + "hvd_flash_dkv/hvd_flash_dkv/pallas_call:", 5.0),
    ("%fusion.7 = f32[10] fusion(%g)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/pack/concatenate:", 0.5),
    ("%psum.8 = f32[10]{0} all-reduce(f32[10]{0} %fusion.7)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/reduce/psum:", 1.0),
    ("%fusion.9 = f32[10] fusion(%psum.8)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/unpack/dynamic_slice:", 0.25),
    ("%psum.10 = f32[]{:T(128)} all-reduce(f32[] %loss)",
     "jit(s)/hvd_loss_allreduce/psum:", 0.125),
    ("%fusion.11 = f32[10] fusion(%p)",
     "jit(s)/hvd_optimizer_update/add:", 0.75),
    ("%copy-done.12 = f32[10] copy-done(%c)", "", 0.375),
    ("%while.13 = (s32[]) while(%t)", "jit(s)/while:", 0.125),
]
#: one step of a cell with no attention, one chip and an update that XLA
#: folded into the backward convolutions
CONV_STEP = [
    ("%fusion.1 = bf16[8] fusion(%p)", "jit(s)/jvp(hvd_forward)/ResNet/"
     "conv_general_dilated:", 3.0),
    ("%fusion.2 = f32[8] fusion(%p)", "jit(s)/transpose(jvp(hvd_forward))/"
     "ResNet/conv_general_dilated:", 5.0),
]
#: a GPT step of a program that names neither its kernels nor its buckets
UNNAMED_STEP = [
    ("%fusion.1 = bf16[8] fusion(%p)", FWD + "dot_general:", 4.0),
    ("%pallas_call.2 = f32[8]" + MOSAIC, FWD + "pallas_call:", 2.0),
    ("%pallas_call.3 = f32[8]" + MOSAIC, BWD + "pallas_call:", 8.0),
]


def _run(step, cfg=CFG, mix=MIX) -> RunRecord:
    ops, t = [], 0.0
    for _ in range(STEPS):
        for name, tf_op, ms in step:
            ops.append(trace.Op(name, t, t + ms * MS, tf_op))
            t += ms * MS
    cell = type("Cell", (), {"cfg": cfg, "mix": mix})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=t, reduced=trace.Reduced(
                         (0.0, t), [trace.ChipTrace(ops, [])], {}))


def _read(metric, run):
    return importlib.import_module(
        "benchmarks.layer_metrics." + metric).read(run)


GPT_MS = {
    "fwd_ms": 4.0 + 1.0 + 2.0, "bwd_ms": 6.0 + 3.0 + 5.0,
    "flash_fwd_ms": 2.0, "flash_dq_ms": 3.0, "flash_dkv_ms": 5.0,
    "grad_pack_ms": 0.5 + 0.25, "optimizer_ms": 0.75,
    "unscoped_ms": 0.375 + 0.125,
}


@pytest.mark.parametrize("metric", sorted(GPT_MS))
def test_each_part_reads_its_own_ops(metric):
    assert math.isclose(_read(metric, _run(GPT_STEP)), GPT_MS[metric])


def test_forward_and_backward_add_up_to_fwd_bwd_ms():
    run = _run(GPT_STEP)
    assert math.isclose(_read("fwd_ms", run) + _read("bwd_ms", run),
                        _read("fwd_bwd_ms", run))


def test_three_flash_parts_add_up_to_flash_ms():
    run = _run(GPT_STEP)
    assert math.isclose(
        sum(_read(f"flash_{k}_ms", run) for k in flash_parts.KERNELS),
        _read("flash_ms", run))


def test_every_op_has_one_owner():
    """Forward, backward, the whole of ``hvd_grad_allreduce``, the loss's
    all-reduce, the update and what no block owns are the whole step."""
    run = _run(GPT_STEP)
    owned = sum(_read(m, run) for m in (
        "fwd_ms", "bwd_ms", "grad_pack_ms", "optimizer_ms", "unscoped_ms"))
    allreduces = 1.0 + 0.125
    assert math.isclose(owned + allreduces,
                        sum(ms for _, _, ms in GPT_STEP))


@pytest.mark.parametrize("kernel", sorted(flash_parts.KERNELS))
def test_flash_part_roofline_is_least_time_over_kernel_time(kernel, capsys):
    need = flash_parts.required(kernel, 8, 12, 1024, 64, causal=True,
                                layers=12)
    least, bound = flops.least_seconds(*need, PEAK)
    share = _read(f"flash_{kernel}_roofline", _run(GPT_STEP))
    assert bound == "compute"
    assert math.isclose(
        share, 100.0 * least / (GPT_MS[f"flash_{kernel}_ms"] * MS))
    assert 0.0 < share < 100.0
    said = capsys.readouterr().out
    assert f"flash_{kernel}_roofline:" in said and "compute-bound" in said


def test_flash_parts_charge_nine_products_where_the_whole_charges_seven():
    """The scores and dP that dq and dkv both compute are required of
    each kernel and of the step only once."""
    shape = dict(causal=True, layers=12)
    parts = [flash_parts.required(k, 8, 12, 1024, 64, **shape)
             for k in ("fwd", "dq", "dkv")]
    whole = flops.flash_train_required(8, 12, 1024, 64, **shape)
    assert math.isclose(sum(p[0] for p in parts), whole[0] * 9.0 / 7.0)
    product = 2.0 * 8 * 12 * 1024 * 1024 * 64 / 2 * 12
    assert [p[0] / product for p in parts] == [2.0, 3.0, 4.0]
    # each kernel reads its inputs itself: more bytes than the whole's
    assert sum(p[1] for p in parts) > whole[1]
    half = flash_parts.required("fwd", 8, 12, 1024, 64, causal=False,
                                layers=12)
    assert math.isclose(half[0], 2.0 * parts[0][0])


def test_optimizer_ms_prints_the_least_time_of_adams_streams(capsys):
    optimizer_ms = importlib.import_module(
        "benchmarks.layer_metrics.optimizer_ms")
    assert optimizer_ms.parameters(CFG, MIX) == 124_439_808
    long_mix = {"rows_per_chip": 1, "arrays": [{"shape": [16384]}]}
    assert optimizer_ms.parameters(CFG, long_mix) \
        == 123_653_376 + 16384 * 768
    optimizer_ms.read(_run(GPT_STEP))
    # 7 x 4 bytes x 124.44 M parameters at 819 GB/s
    assert "least 4.254 ms" in capsys.readouterr().out


NO_SUCH_OPS = [
    (metric, step) for step in ("conv", "unnamed") for metric in (
        "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "flash_fwd_roofline",
        "flash_dq_roofline", "flash_dkv_roofline", "grad_pack_ms",
        "optimizer_ms")]


@pytest.mark.parametrize("metric,step", NO_SUCH_OPS)
def test_a_part_with_no_ops_reads_none_and_does_not_raise(metric, step):
    """A cell without attention or without an update of its own, and the
    parent of the PR that named the kernels: the line leaves the metric
    out."""
    run = _run({"conv": CONV_STEP, "unnamed": UNNAMED_STEP}[step])
    assert _read(metric, run) is None


def test_unnamed_kernels_still_read_as_the_whole():
    run = _run(UNNAMED_STEP)
    assert math.isclose(_read("flash_ms", run), 10.0)
    assert math.isclose(_read("fwd_ms", run), 6.0)
    assert math.isclose(_read("bwd_ms", run), 8.0)
    assert _read("unscoped_ms", run) == 0.0


def test_unscoped_ms_counts_a_loop_once():
    """A compiler-made ``while`` with no metadata lies on the core's line
    over its own body's ops: the envelope and an unscoped body op are one
    stretch of time, and a body op that kept its scope is its block's."""
    ops = [
        trace.Op("%while.1 = (s32[]) while(%t)", 0.0, 10 * MS, ""),
        trace.Op("%dynamic-update-slice.2 = f32[8] dynamic-update-slice(%a)",
                 1 * MS, 4 * MS, ""),
        trace.Op("%fusion.3 = f32[8] fusion(%a)", 5 * MS, 7 * MS,
                 FWD + "hvd_loss/scatter-add:"),
        trace.Op("%copy-done.4 = f32[8] copy-done(%c)", 12 * MS, 13 * MS, ""),
    ]
    cell = type("Cell", (), {"cfg": CFG, "mix": MIX})
    run = RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=1, window_s=13 * MS,
                    reduced=trace.Reduced(
                        (0.0, 13 * MS), [trace.ChipTrace(ops, [])], {}))
    assert math.isclose(_read("unscoped_ms", run), (10 - 2) + 1)
    assert math.isclose(_read("fwd_ms", run), 2.0)


def test_the_programs_resnet_constant_is_the_required_count():
    """``utils/flops.RESNET50_TRAIN_FLOPS_PER_IMG`` feeds the program's own
    MFU gauge and ``bench.py``; it counted multiply-adds as operations
    (12.27e9) until it was held to the conv-by-conv count."""
    import json
    import os

    import benchmark_tiny
    from horovod_tpu.utils.flops import RESNET50_TRAIN_FLOPS_PER_IMG

    with open(os.path.join(benchmark_tiny.REPO, "benchmarks", "configs",
                           "resnet50.json")) as fh:
        cfg = json.load(fh)
    need = flops.resnet50_train_flops_per_image(cfg, 224)
    assert math.isclose(RESNET50_TRAIN_FLOPS_PER_IMG, need, rel_tol=1e-3)
