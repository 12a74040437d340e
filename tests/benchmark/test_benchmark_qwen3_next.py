"""ISSUE 26's benchmark tests: the configuration ``qwen3_next_80b_a3b``, the
traffic ``seq8k-b1``, the cell ``qwen3next-8k`` and its per-layer metrics.

They stand in a file of their own because the other files of this directory
are the benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under
``paths``) and a PR that changes the program may only add beside them.
Which cells list which metric, every cell's files and the toy benchmarks'
form follow ``BENCHMARK.json`` in ``test_benchmark_lists.py``,
``test_benchmark_harness.py`` and ``test_benchmark_form.py`` (PR 40)."""

import json
import math
import os

import pytest

import benchmark_tiny
import benchmark_tiny_qwen
from benchmarks.harness import flops, peaks, trace
from benchmarks.harness import qwen3_next_parts as parts
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)


def _qwen_cfg():
    with open(os.path.join(benchmark_tiny.REPO, "benchmarks", "configs",
                           "qwen3_next_80b_a3b.json")) as fh:
        return json.load(fh)


# -- required operations, one chip's share ------------------------------------


def test_qwen3_next_parameter_count_by_hand():
    cfg = _qwen_cfg()
    d = 2048
    delta = d * 12288 + d * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * d
    attention = d * 8192 + 2 * d * 512 + 2 * 256 + 4096 * d
    moe = d * 512 + 16 * 3 * d * 512 + 3 * d * 512 + d
    layers = 3 * delta + attention + 4 * (moe + 2 * d)
    assert parts.parameters(cfg) == layers + 2 * 18992 * d + d == 424_340_544
    assert parts.layer_counts(cfg) == (3, 1)
    # the uncut model by the same count: 79.67 B
    whole = dict(cfg, num_hidden_layers=48, num_experts=512,
                 vocab_size=151936)
    assert math.isclose(parts.parameters(whole), 79.674e9, rel_tol=1e-4)


def test_qwen3_next_train_flops_per_token():
    """2 per matmul parameter a token multiplies (the held experts at the
    10 x 16 / 512 assignments an even router sends), the recurrence's three
    128 x 128 products a value head, causal attention 2 s h d; times three
    for training."""
    cfg = _qwen_cfg()
    assert parts.expected_assignments_per_token(cfg) == 0.3125
    d = 2048
    params = (3 * (d * 12288 + d * 64 + 4 * 8192 + 4096 * d)
              + (d * 8192 + 2 * d * 512 + 4096 * d)
              + 4 * (d * 512 + 3 * d * 512 + d + 0.3125 * 3 * d * 512)
              + d * 18992)
    assert math.isclose(parts.matmul_params_per_token(cfg), params)
    forward = 2 * params + 3 * (2 * 3 * 32 * 128 * 128) \
        + 2 * 8192 * 16 * 256
    assert math.isclose(parts.forward_flops_per_token(cfg, 8192), forward)
    assert math.isclose(forward, 452.6e6, rel_tol=1e-4)
    assert math.isclose(parts.train_flops_per_token(cfg, 8192), 1357.8e6,
                        rel_tol=1e-4)


def test_qwen3_next_scan_and_expert_requirements_by_hand():
    cfg = _qwen_cfg()
    ops, nbytes = parts.scan_train_required(cfg, 1, 8192)
    per_layer = 3 * 8192 * 32 * 3 * 2 * 128 * 128
    assert ops == 3 * per_layer
    tensors = 8192 * 32 * (4 * 128 * 2 + 2 * 4)
    states = 32 * 128 * 128 * 128 * 4          # 128 chunks of 64 tokens
    assert nbytes == 3 * (3 * tensors + 4 * states)
    ops, nbytes, rows = parts.experts_train_required(cfg, 1, 8192)
    assert rows == 2560                         # 160 tokens an expert
    assert ops == 4 * 3 * 2 * 2560 * 3 * 2048 * 512
    weights = 16 * 3 * 2048 * 512
    rows_bytes = 2560 * (2 * 2048 + 3 * 512) * 2
    assert nbytes == 4 * (2 * (weights * 2 + rows_bytes)
                          + weights * 4 + rows_bytes)
    # both far under the chip's peaks for a step: memory-bound shares

    peak = peaks.PEAKS["TPU v5 lite"]
    assert flops.least_seconds(ops, nbytes, peak)[1] == "memory"


# -- the files' form ----------------------------------------------------------


def test_qwen3_next_files_state_the_cut_and_the_traffic_of_its_cell():
    """The configuration's file says what `BENCHMARK.json` says was
    reduced, keeps the published counts beside the cut ones, and names
    the deployment; the traffic is ISSUE 26's."""
    with open(os.path.join(benchmark_tiny.REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    entry = next(c for c in spec["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    with open(os.path.join(benchmark_tiny.REPO, entry["file"])) as fh:
        cfg = json.load(fh)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["source"] == entry["source"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["router_num_experts"] == 512 and cfg["first_expert"] == 0
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert "32 chips" in cfg["deployment"] and cfg["assumed"]
    # no width is cut: the keys the contract forbids in `reduced`
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "linear_key_head_dim",
                "linear_value_head_dim"):
        assert key not in cfg["reduced"]
    with open(os.path.join(benchmark_tiny.REPO, "benchmarks", "traffic",
                           "seq8k-b1.json")) as fh:
        mix = json.load(fh)
    assert mix == {
        "rows_per_chip": 1, "dataset_rows_per_chip": 64,
        "arrays": [{"name": "ids", "shape": [8192], "dtype": "int32",
                    "low": 0, "high": "vocab_size"}],
        "items_per_row": 8192, "rate_metric": "tokens_per_s_chip"}

# -- Qwen3-Next's layers -----------------------------------------------------

QWEN_CFG = {
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
    "num_key_value_heads": 2, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "num_experts": 16, "router_num_experts": 512, "num_experts_per_tok": 10,
    "vocab_size": 18992}
QWEN_MIX = {"rows_per_chip": 1, "arrays": [{"shape": [8192]}]}
QF = "jit(s)/jvp(hvd_forward)/Qwen3Next/layers_0/"
QB = "jit(s)/transpose(jvp(hvd_forward))/Qwen3Next/layers_0/"
#: one step: (HLO text, tf_op, start ms, end ms); the scan's ``while`` lies
#: over its own body's ops
QWEN_STEP = [
    ("%fusion.1 = bf16[8] fusion(%p)",
     QF + "linear_attn/hvd_gdn/in_proj_qkvz/dot_general:", 0, 4),
    ("%fusion.2 = bf16[8] fusion(%p)",
     QF + "linear_attn/hvd_gdn/hvd_gdn_conv/mul:", 4, 5),
    ("%fusion.3 = f32[8] fusion(%p)",
     QF + "linear_attn/hvd_gdn/hvd_gdn_scan/local/dot_general:", 5, 7),
    ("%while.4 = (s32[]) while(%t)",
     QF + "linear_attn/hvd_gdn/hvd_gdn_scan/carry/while:", 7, 13),
    ("%fusion.5 = f32[8] fusion(%p)",
     QF + "linear_attn/hvd_gdn/hvd_gdn_scan/carry/while/body/dot_general:",
     8, 10),
    ("%fusion.6 = f32[8] fusion(%p)",
     QF + "linear_attn/hvd_gdn/hvd_gdn_scan/carry/while/body/add:", 10, 12),
    ("%fusion.7 = f32[8] fusion(%p)",
     QF + "mlp/hvd_moe/hvd_moe_route/top_k:", 13, 15),
    ("%fusion.8 = f32[8] fusion(%p)",
     QF + "mlp/hvd_moe/hvd_moe_experts/dot_general:", 15, 16),
    ("%fusion.9 = bf16[8] fusion(%p)",
     QF + "mlp/hvd_moe/hvd_moe_experts/mul:", 16, 16.5),
    ("%fusion.10 = bf16[8] fusion(%p)",
     QF + "mlp/hvd_moe/hvd_moe_shared/dot_general:", 16.5, 18),
    ("%fusion.11 = f32[8] fusion(%p)",
     QB + "linear_attn/hvd_gdn/hvd_gdn_scan/local/dot_general:", 18, 21),
    ("%fusion.12 = f32[8] fusion(%p)",
     QB + "mlp/hvd_moe/hvd_moe_route/scatter-add:", 21, 22),
    ("%fusion.13 = f32[8] fusion(%p)",
     QB + "mlp/hvd_moe/hvd_moe_experts/dot_general:", 22, 24),
    ("%fusion.14 = f32[10] fusion(%p)",
     "jit(s)/hvd_optimizer_update/add:", 24, 25),
]
QWEN_MS = {"gdn_ms": 13.0 + 3.0, "gdn_scan_ms": 8.0 + 3.0,
           "moe_ms": 5.0 + 3.0, "moe_route_ms": 2.0 + 1.0}


def _qwen_run(step=QWEN_STEP) -> RunRecord:
    ops = [trace.Op(name, (25 * i + a) * MS, (25 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": QWEN_CFG, "mix": QWEN_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=25 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 25 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


@pytest.mark.parametrize("metric", sorted(QWEN_MS))
def test_each_qwen_part_counts_a_scan_once(metric):
    """Interval arithmetic: the ``while`` of the scan is on the core's line
    with its body's ops, and a sum of durations would count 4 ms twice."""
    assert math.isclose(_read(metric, _qwen_run()), QWEN_MS[metric])


def test_scan_roofline_is_least_time_over_scan_time(capsys):
    need = parts.scan_train_required(QWEN_CFG, 1, 8192)
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "memory"
    got = _read("gdn_scan_roofline", _qwen_run())
    assert math.isclose(got, 100.0 * least / (11.0 * MS))
    assert 0 < got < 100
    assert "gdn_scan_roofline:" in capsys.readouterr().out


def test_experts_roofline_is_least_time_over_the_products_time(capsys):
    ops, nbytes, rows = parts.experts_train_required(QWEN_CFG, 1, 8192)
    least, _ = flops.least_seconds(ops, nbytes, PEAK)
    got = _read("moe_experts_roofline", _qwen_run())
    # the products 1.0 + 2.0 and the SiLU between them 0.5
    assert math.isclose(got, 100.0 * least / (3.5 * MS))
    assert "2560 expected assignments a layer" in capsys.readouterr().out


#: a step whose expert layer ran three tiles: the loops' bodies under
#: ``hvd_moe_experts`` (two instructions forward, one backward) three times
#: each, and a flash kernel forward and backward beside them
QA = QF.replace("layers_0", "layers_3")
TILED_STEP = [row for row in QWEN_STEP if "hvd_moe_experts" not in row[1]] + [
    (name, QF + "mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     13 + i, 13.5 + i)
    for i in range(3) for name in ("%fusion.20 = f32[8] fusion(%p)",
                                   "%fusion.21 = f32[8] fusion(%p)")] + [
    ("%fusion.22 = f32[8] fusion(%p)",
     QB + "mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     22 + i / 2, 22.5 + i / 2) for i in range(3)] + [
    ("%hvd_flash_fwd.30 = f32[8]" + MOSAIC,
     QA + "self_attn/hvd_flash_fwd/pallas_call:", 18, 19),
    ("%hvd_flash_dkv.31 = f32[8]" + MOSAIC,
     QA + "self_attn/hvd_flash_dkv/pallas_call:", 19, 21.5)]


@pytest.mark.parametrize("step,tiles", [(QWEN_STEP, 1.0), (TILED_STEP, 3.0)])
def test_moe_tiles_counts_how_often_a_loop_body_ran(step, tiles):
    """Every instruction of a body runs once a tile, whatever their number
    and in the forward and the backward loop alike."""
    assert math.isclose(_read("moe_tiles", _qwen_run(step)), tiles)


def test_flash_gqa_roofline_takes_heads_and_layers_from_its_own_keys(capsys):
    """One full-attention layer of the four, 16 q heads of 256: seven
    products over the Mosaic kernels' 3.5 ms a step."""
    need = flops.flash_train_required(1, 16, 8192, 256, causal=True,
                                      layers=1)
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read("flash_gqa_roofline", _qwen_run(TILED_STEP))
    assert math.isclose(got, 100.0 * least / (3.5 * MS))
    assert "flash_gqa_roofline:" in capsys.readouterr().out
    # a GPT cell's configuration has no such keys: nothing to read
    assert _read("flash_gqa_roofline", _run(GPT_STEP)) is None
    assert _read("flash_gqa_roofline", _qwen_run()) is None


@pytest.mark.parametrize("metric", sorted(QWEN_MS) + [
    "gdn_scan_roofline", "moe_experts_roofline", "moe_tiles"])
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_a_qwen_part_reads_none_where_the_program_has_no_such_scope(
        metric, step):
    """The parent of the PR that brought the scopes, and a cell of another
    configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    run.cell.cfg, run.cell.mix = QWEN_CFG, QWEN_MIX
    assert _read(metric, run) is None


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_qwen_root(tmp_path_factory):
    return benchmark_tiny_qwen.make(str(tmp_path_factory.mktemp("bench")))


def test_tiny_qwen_cell_runs_end_to_end(tiny_qwen_root, world, capsys):
    """Both mixers, the chunked scan and the routed experts (held 2..5 of
    8) through ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_qwen_root, "tiny-qwen", 1)
    _well_formed(result, "tiny-qwen", 1)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out = capsys.readouterr().out
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap",
                 "final_loss", "nonfinite_losses"):
        assert f"check: {name} = " in out and "limit" in out


def test_tiny_qwen_adds_files_and_entries_and_edits_none(tiny_qwen_root,
                                                         tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_qwen_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/qwen3_next_tiny.json",
                     "configs/qwen3_next_tiny.py", "traffic/seq128-b2q.json"}
    with open(os.path.join(plain, "BENCHMARK.json")) as fh:
        had = json.load(fh)
    with open(os.path.join(tiny_qwen_root, "BENCHMARK.json")) as fh:
        now = json.load(fh)
    for key in ("configs", "workloads"):
        assert now[key][:-1] == had[key]
    for key in ("end_to_end", "per_layer"):
        for mine, theirs in zip(now[key], had[key], strict=True):
            extra = ["tiny-qwen"] if "qwen3next-8k" in theirs.get(
                "workloads", ()) or theirs["name"] == "steps_done" else []
            assert mine == dict(theirs, **(
                {"workloads": theirs["workloads"] + extra} if extra else {}))


