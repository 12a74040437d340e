"""ISSUE 38's benchmark tests: the configuration ``mellum2_12b_a2p5b``, the
traffic ``seq16k-b1-m2``, the cell ``mellum2-16k`` and its eight readers.

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  Which cells
list which metric, every cell's files and the toy benchmarks' form follow
``BENCHMARK.json`` in ``test_benchmark_lists.py``, ``test_benchmark_harness.py``
and ``test_benchmark_form.py`` (PR 40)."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
import benchmark_tiny_mellum2
from benchmarks.configs import mellum2_12b_a2p5b as adapter
from benchmarks.harness import check, flash_parts, flops, peaks, trace
from benchmarks.harness import mellum2_parts as parts
from benchmarks.harness.spec import Spec
from benchmarks.references import common, mellum2
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_part_scopes import _fusion
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

CELL = "mellum2-16k"
SLIDING, FULL = parts.SLIDING, parts.FULL
KERNEL_SHARES = ["flash_swa_fwd_roofline", "flash_swa_dq_roofline",
                 "flash_swa_dkv_roofline"]
NEW_READERS = ["attn_window_ms", "attn_full_ms", "flash_swa_roofline",
               *KERNEL_SHARES, "flash_full_roofline", "swa_experts_roofline"]
#: the accepted readers that list the new cell too (they go by scope, by
#: kernel name or by JAX's mark, and find their ops here)
LISTED = ["flash_ms", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
          "flash_layout_ms", "attn_proj_ms", "moe_ms", "moe_route_ms",
          "moe_tiles", "recompute_ms", "recompute_mixer_ms",
          "recompute_moe_ms", "head_ms", "loss_ms", "fwd_ms", "bwd_ms",
          "unscoped_ms", "grad_pack_ms"]


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


def _cfg():
    return _json("benchmarks", "configs", "mellum2_12b_a2p5b.json")


# -- parameters and required operations, one chip's share, by hand --------------


def test_mellum2_parameter_count_by_hand():
    cfg = _cfg()
    d = 2304
    w_q, w_kv, w_o = d * 32 * 128, d * 4 * 128, 32 * 128 * d
    assert (w_q, w_kv, w_o) == (9_437_184, 1_179_648, 9_437_184)
    attention = w_q + 2 * w_kv + w_o
    assert attention == 21_233_664 == parts.attention_matmul_params(cfg)
    router, expert = d * 64, 3 * d * 896
    assert (router, expert) == (147_456, 6_193_152)
    layer = attention + router + 8 * expert + 2 * d
    table = 12288 * d
    assert (layer, table) == (70_930_944, 28_311_552)
    n = parts.parameters(cfg)
    assert n == 4 * layer + 2 * table + d == 340_349_184 \
        == cfg["deployment_parameters"]
    # a window layer and a full layer hold the same parameters
    shapes = mellum2.param_shapes(cfg)
    assert {k.split("/", 1)[1]: v for k, v in shapes.items()
            if k.startswith("layers_0/")} == {
        k.split("/", 1)[1]: v for k, v in shapes.items()
        if k.startswith("layers_3/")}
    # 16 B a parameter for training, 20 B while run.py holds the benchmark's
    # weights through the checked steps, 36 B in the reference's update
    assert math.isclose(16 * n, 5.45e9, rel_tol=1e-3)
    assert math.isclose(20 * n, 6.81e9, rel_tol=1e-3)
    assert math.isclose(36 * n, 12.25e9, rel_tol=1e-3)
    # 16 held experts: 538 M and 19.4 GB in the reference's update; with a
    # quarter of the vocabulary too (a 4-chip share) 595 M and 21.4 GB
    wider = parts.parameters(dict(cfg, num_experts=16))
    assert math.isclose(wider, 538.5e6, rel_tol=1e-3)
    assert math.isclose(36 * wider, 19.4e9, rel_tol=2e-3)
    widest = parts.parameters(dict(cfg, num_experts=16, vocab_size=24576))
    assert math.isclose(widest, 595e6, rel_tol=1e-3)
    assert math.isclose(36 * widest, 21.4e9, rel_tol=2e-3)
    # the uncut model by the same count: 12.15 B
    whole = dict(cfg, num_hidden_layers=28, num_experts=64,
                 vocab_size=98304)
    assert math.isclose(parts.parameters(whole), 12.15e9, rel_tol=1e-3)


def test_mellum2_train_flops_per_token_by_hand():
    cfg = _cfg()
    assert parts.layer_kinds(cfg) == [SLIDING, SLIDING, SLIDING, FULL]
    assert (parts.layers_of(cfg, SLIDING), parts.layers_of(cfg, FULL)) \
        == (3, 1)
    # a layer's products: the projections, the router and the expected
    # 8 x 8 / 64 = 1 held assignment a token
    layer = 2 * (21_233_664 + 147_456 + 1 * 6_193_152)
    assert math.isclose(4 * layer, 220.6e6, rel_tol=1e-4)
    head = 2 * 2304 * 12288
    assert math.isclose(head, 56.6e6, rel_tol=1e-3)
    # the full layer: 8192.5 causal pairs a row, two products of 2 x 128 a
    # head a pair
    assert parts.allowed_pairs(cfg, FULL, 16384) == 16384 * 16385 // 2
    full = 4 * 128 * 32 * 8192.5
    assert math.isclose(full, 134.2e6, rel_tol=1e-3)
    # a window layer: 1024 keys a row, the first 1024 rows fewer: 16 253 440
    # pairs a head, 992.0 a row
    pairs = sum(min(i + 1, 1024) for i in range(16384))
    assert pairs == 16384 * 1024 - 1024 * 1023 // 2 == 16_253_440 \
        == parts.allowed_pairs(cfg, SLIDING, 16384)
    assert math.isclose(pairs / 16384, 992.0, abs_tol=0.05)
    window = 4 * 128 * 32 * pairs / 16384
    assert math.isclose(3 * window, 48.8e6, rel_tol=1e-3)
    assert math.isclose(parts.attention_flops_per_token(cfg, SLIDING, 16384),
                        window)
    forward = 4 * layer + head + full + 3 * window
    assert math.isclose(parts.forward_flops_per_token(cfg, 16384), forward)
    assert math.isclose(forward, 460.2e6, rel_tol=1e-4)
    assert math.isclose(parts.train_flops_per_token(cfg, 16384), 1380.6e6,
                        rel_tol=1e-4)
    assert math.isclose(adapter.flops_per_item(cfg, _json(
        "benchmarks", "traffic", "seq16k-b1-m2.json")), 3 * forward)
    # attention under two masks is 40% of it: the full layer 29%, the three
    # window layers 11%; the layers' products 48%, the head 12%
    assert 0.395 < (full + 3 * window) / forward < 0.400
    assert 0.290 < full / forward < 0.294
    assert 0.104 < 3 * window / forward < 0.108
    assert 0.477 < 4 * layer / forward < 0.481
    assert 0.121 < head / forward < 0.125
    # a step: 22.6 TFLOP, least 114.8 ms at the v5e's peak
    step = 16384 * 3 * forward
    assert math.isclose(step, 22.62e12, rel_tol=1e-3)
    assert math.isclose(step / peaks.PEAKS["TPU v5 lite"].flops, 0.1148,
                        rel_tol=2e-3)
    # a window that is longer than the sequence is the causal mask
    assert parts.allowed_pairs(dict(cfg, sliding_window=20000), SLIDING,
                               16384) == 16384 * 16385 // 2


def test_mellum2_flash_and_expert_requirements_by_hand():
    cfg = _cfg()
    tensor, rows = 32 * 16384 * 128 * 2, 32 * 16384 * 4
    for kind, layers, pairs in ((SLIDING, 3, 16_253_440),
                                (FULL, 1, 16384 * 16385 // 2)):
        product = 2.0 * 32 * 128 * pairs
        ops, nbytes = parts.flash_train_required(cfg, kind, 1, 16384)
        assert ops == layers * 7 * product
        assert nbytes == layers * ((4 * tensor + rows)
                                   + (8 * tensor + 2 * rows))
        # each kernel's own products: 2 / 3 / 4, nine where the whole has
        # seven
        for kernel, (products, tensors, stats) in flash_parts.KERNELS.items():
            k_ops, k_bytes = parts.flash_kernel_required(
                cfg, kind, kernel, 1, 16384)
            assert k_ops == layers * products * product, (kind, kernel)
            assert k_bytes == layers * (tensors * tensor + stats * rows)
        assert sum(parts.flash_kernel_required(cfg, kind, k, 1, 16384)[0]
                   for k in flash_parts.KERNELS) == ops / 7 * 9
    # the full layer's count is the accepted one's (s^2 / 2 pairs there,
    # s (s + 1) / 2 here): compute-bound; the window layers' work is an
    # eighth of it a layer and the same tensors: still compute-bound
    accepted = flops.flash_train_required(1, 32, 16384, 128, causal=True,
                                          layers=1)
    mine = parts.flash_train_required(cfg, FULL, 1, 16384)
    assert math.isclose(mine[0] / accepted[0], 16385 / 16384)
    assert mine[1] == accepted[1]
    for kind in (SLIDING, FULL):
        assert flops.least_seconds(*parts.flash_train_required(
            cfg, kind, 1, 16384), PEAK)[1] == "compute"
    # the experts: 16 384 expected assignments a layer, four layers, as the
    # accepted count reads this configuration's keys
    from benchmarks.harness import qwen3_next_parts as moe_parts

    ops, nbytes, assignments = moe_parts.experts_train_required(
        cfg, 1, 16384)
    assert assignments == 16384 * 8 * 8 / 64 == 16384
    assert ops == 4 * 3 * 2 * 16384 * 3 * 2304 * 896
    weights = 8 * 3 * 2304 * 896
    rows_bytes = 16384 * (2 * 2304 + 3 * 896) * 2
    assert nbytes == 4 * (2 * (weights * 2 + rows_bytes)
                          + weights * 4 + rows_bytes)
    # 2048 rows an expert: the products bind, not the weights' traffic
    assert flops.least_seconds(ops, nbytes, PEAK)[1] == "compute"


# -- the files' form ----------------------------------------------------------


def test_mellum2_files_state_the_cut_and_the_traffic_of_its_cell():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"]
                 if c["name"] == "mellum2_12b_a2p5b")
    cfg = _json(entry["file"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["source"] == entry["source"] \
        == "https://huggingface.co/JetBrains/" \
           "Mellum2-12B-A2.5B-Instruct/blob/main/config.json"
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 12288)
    assert cfg["router_num_experts"] == 64 and cfg["first_expert"] == 0
    assert "8 chips" in cfg["deployment"]
    assert {"training_recipe", "loss", "no_head_norm_no_sink",
            "multi_token_prediction", "weights", "expert_capacity",
            "intermediate_size"} <= set(cfg["assumed"])
    assert "36 B" in cfg["reduced"]["num_experts"]
    assert (cfg["memory_gb"]["training_16_B"], cfg["memory_gb"][
        "run_py_setup_20_B"], cfg["memory_gb"]["reference_update_36_B"]) \
        == (5.45, 6.81, 12.25)
    # every number of the source's config under its key, but the three cut;
    # the nested groups whole (the lists of 28 kinds too: the layers run
    # are their first ``num_hidden_layers``)
    source = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
        "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "use_sliding_window": True}
    assert {k: cfg[k] for k in source} == source
    assert parts.layer_kinds(cfg) == mellum2.layer_kinds(cfg) \
        == [SLIDING, SLIDING, SLIDING, FULL]
    # no width is cut: the keys the contract forbids in `reduced`
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok",
                "sliding_window", "rope_parameters"):
        assert key not in cfg["reduced"]
    # the load is bounded as GShard bounds it, an expert's capacity of a
    # group one tile of the expert layer
    assert (cfg["moe_group_rows"], cfg["moe_capacity_factor"]) == (2048, 1.0)
    assert mellum2.capacity(cfg, 2048) == 256
    assert "2006.16668" in cfg["assumed"]["expert_capacity"]
    # the q projections' seeded deviation by PR 30's rule, one value for all
    # layers: the window layers' scores (three layers of four, at most 1024
    # keys a row) at sqrt(2 ln 1024); the full layer's, which carry the
    # attention factor squared, then read 6.07.  ISSUE 38's first value put
    # the full layer at sqrt(2 ln 16384) and the window layers at 2.70:
    # assumed.weights says what the chip read there
    q_std = cfg["q_proj_initializer_range"]
    assert "q_proj_initializer_range" in cfg["assumed"]["weights"]
    window = q_std * 2304 * 0.02 * math.sqrt(128) * 128 ** -0.5
    full = window * 1.2772588722239782 ** 2
    assert abs(window - math.sqrt(2 * math.log(1024))) < 0.01
    assert abs(full - 6.07) < 0.01
    assert abs(0.0586 * 2304 * 0.02 * 1.2772588722239782 ** 2
               - math.sqrt(2 * math.log(16384))) < 0.01
    assert math.isclose(0.02 * 2304 * 0.02, 0.92, abs_tol=0.005)
    # the traffic is seq16k-b1's numbers in a file of its own, but a
    # dataset of 128 rows: no row twice in a window and the checked steps
    mix = _json("benchmarks", "traffic", "seq16k-b1-m2.json")
    assert mix == dict(_json("benchmarks", "traffic", "seq16k-b1.json"),
                       dataset_rows_per_chip=128) == {
        "rows_per_chip": 1, "dataset_rows_per_chip": 128,
        "arrays": [{"name": "ids", "shape": [16384], "dtype": "int32",
                    "low": 0, "high": "vocab_size"}],
        "items_per_row": 16384, "rate_metric": "tokens_per_s_chip"}


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(benchmark_tiny.REPO, "benchmarks", "references",
                        "mellum2.py")
    with open(path) as fh:
        code = [line for line in fh if line.startswith(("import ", "from "))]
    assert code and not any("horovod_tpu" in line for line in code)


# -- the control --------------------------------------------------------------

TOY = benchmark_tiny_mellum2.MELLUM2_TINY
#: The cell's limits are read on the chip at the cell's size.  The toy is
#: float32, four layers and 64 tokens: a sound program reads 1e-6 and its
#: float8 control 0.08 and more, so the toy holds the control to a limit
#: between those.
TOY_LIMITS = dict(adapter.LIMITS, grad_sketch_gap=0.02)


def _toy_batch(seed, rows=2, length=64):
    return (np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], (rows, length)).astype(np.int32),)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_mellum2(seed):
    ref = {"init": lambda s: mellum2.seeded_weights(TOY, seed),
           "loss": lambda p: mellum2.loss_fn(TOY, p), "optimizer": "adam",
           "lr": 1e-4}
    batches = [_toy_batch(seed * 10 + i) for i in range(3)]
    numbers = check.first_steps_numbers(
        common.follow(ref, 0, batches, 2, "fp8"),
        common.follow(ref, 0, batches, 2))
    correct, lines = check.verdict(
        numbers, {k: TOY_LIMITS[k] for k in numbers})
    assert not correct, lines
    assert numbers["grad_sketch_gap"] > 2 * TOY_LIMITS["grad_sketch_gap"]


# -- the eight readers on a hand-built trace -------------------------------------

M2_CFG = {
    "num_hidden_layers": 4, "layer_types": [SLIDING, SLIDING, SLIDING, FULL],
    "hidden_size": 2304, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 1024,
    "moe_intermediate_size": 896, "num_experts": 8,
    "router_num_experts": 64, "num_experts_per_tok": 8,
    "vocab_size": 12288}
M2_MIX = {"rows_per_chip": 1, "arrays": [{"shape": [16384]}]}
MF = "jit(s)/jvp(hvd_forward)/Mellum2/"
MB = "jit(s)/transpose(jvp(hvd_forward))/Mellum2/jvp(hvd_forward)/" \
     "Mellum2/checkpoint/"
W = "layers_0/self_attn/hvd_attn/hvd_attn_window/"
FL = "layers_3/self_attn/hvd_attn/hvd_attn_full/"


def _kernel(i, name, path, start, end):
    return (f"%{name}.{i} = bf16[8]" + MOSAIC,
            path + f"jit(_call)/{name}/{name}/pallas_call:", start, end)


#: one step of 60 ms: (HLO text, tf_op, start ms, end ms)
M2_STEP = [
    _fusion(0, MF + "hvd_rotary_tables/cos:", 0, 0.5),
    ("%copy-done.23 = f32[10] copy-done(%c)", "", 0.5, 1),
    _fusion(1, MF + W + "hvd_attn_qkv/q_proj/dot_general:", 1, 3),
    _fusion(2, MF + W + "hvd_flash_layout/transpose:", 3, 3.5),
    _kernel(3, "hvd_flash_fwd", MF + W, 3.5, 5.5),
    _fusion(4, MF + W + "hvd_attn_out/o_proj/dot_general:", 5.5, 6),
    _fusion(5, MF + "layers_0/mlp/hvd_moe/hvd_moe_route/top_k:", 6, 7),
    ("%while.6 = (s32[]) while(%t)", MF + "layers_0/mlp/hvd_moe/while:",
     7, 9),
    _fusion(7, MF + "layers_0/mlp/hvd_moe/while/body/hvd_moe_experts/"
            "dot_general:", 7, 9),
    _fusion(8, MF + FL + "hvd_attn_qkv/q_proj/dot_general:", 10, 12),
    _kernel(9, "hvd_flash_fwd", MF + FL, 12, 20),
    _fusion(10, MF + FL + "hvd_attn_out/o_proj/dot_general:", 20, 21),
    _fusion(11, MF + "hvd_head/dot_general:", 21, 24),
    _fusion(20, MF[:-8] + "hvd_loss/reduce_sum:", 24, 24.5),
    # the full layer's recompute and backward
    _fusion(21, MB + "rematted_computation/layers_3/mlp/hvd_moe/"
            "hvd_moe_route/dot_general:", 24.5, 25),
    _fusion(12, MB + "rematted_computation/" + FL
            + "hvd_attn_qkv/q_proj/dot_general:", 25, 26),
    _kernel(13, "hvd_flash_dq", MB + FL, 26, 35),
    _kernel(14, "hvd_flash_dkv", MB + FL, 35, 45),
    # the window layer's
    _kernel(15, "hvd_flash_dq", MB + W, 45, 48),
    _kernel(16, "hvd_flash_dkv", MB + W, 48, 52),
    _fusion(17, MB + W + "hvd_attn_qkv/q_proj/transpose:", 52, 55),
    _fusion(18, MB + "layers_0/mlp/hvd_moe/while/body/hvd_moe_experts/"
            "dot_general:", 55, 58),
    ("%fusion.22 = f32[10] fusion(%g)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/pack/concatenate:", 58, 58.5),
    ("%fusion.19 = f32[10] fusion(%p)", "jit(s)/hvd_optimizer_update/add:",
     58.5, 60),
]


def _m2_run(step=M2_STEP, cfg=M2_CFG) -> RunRecord:
    ops = [trace.Op(name, (60 * i + a) * MS, (60 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": cfg, "mix": M2_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=60 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 60 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


def test_attn_window_ms_and_attn_full_ms_read_their_kinds():
    run = _m2_run()
    # window: q 2, layout 0.5, forward kernel 2, o_proj 0.5; dq 3, dkv 4,
    # the transposed projection 3
    assert math.isclose(_read("attn_window_ms", run), 5.0 + 10.0)
    # full: q 2, forward kernel 8, o_proj 1; the recompute 1, dq 9, dkv 10
    assert math.isclose(_read("attn_full_ms", run), 11.0 + 20.0)
    # the accepted readers find the kernels and scopes of both kinds
    assert math.isclose(_read("flash_ms", run), 2 + 8 + 9 + 10 + 3 + 4)
    assert math.isclose(_read("flash_fwd_ms", run), 10.0)
    assert math.isclose(_read("attn_proj_ms", run), 2.5 + 3 + 1 + 3)
    assert math.isclose(_read("flash_layout_ms", run), 0.5)
    assert math.isclose(_read("recompute_ms", run), 1.5)
    assert math.isclose(_read("recompute_mixer_ms", run), 1.0)
    assert math.isclose(_read("recompute_moe_ms", run), 0.5)
    assert math.isclose(_read("moe_ms", run), 3.0 + 0.5 + 3.0)
    assert math.isclose(_read("moe_route_ms", run), 1.5)
    assert math.isclose(_read("moe_tiles", run), 1.0)
    assert math.isclose(_read("head_ms", run), 3.0)
    assert math.isclose(_read("loss_ms", run), 0.5)
    assert math.isclose(_read("grad_pack_ms", run), 0.5)


def test_every_reader_the_cell_lists_returns_a_number_on_such_a_trace():
    """A CPU run has no device plane to trace, so what the chip's traced run
    prints is held on the chip (PERF.md section 6); here every reader the
    cell lists that reads the device trace finds its ops in a step shaped
    as this model's (both kinds of layer, the kernels under them, a marked
    recompute, the expert loop, head, loss and pack)."""
    run = _m2_run()
    cell = Spec(benchmark_tiny.REPO).cell(CELL)
    for name in LISTED + NEW_READERS:
        assert name in cell.per_layer
        value = _read(name, run)
        assert value is not None and value > 0, name


@pytest.mark.parametrize("metric,kind,ms", [
    ("flash_swa_roofline", SLIDING, 2.0 + 3.0 + 4.0),
    ("flash_full_roofline", FULL, 8.0 + 9.0 + 10.0)])
def test_a_kinds_roofline_is_least_time_over_its_own_kernels(
        capsys, metric, kind, ms):
    need = parts.flash_train_required(M2_CFG, kind, 1, 16384)
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read(metric, _m2_run())
    assert math.isclose(got, 100.0 * least / (ms * MS))
    assert f"{metric}:" in capsys.readouterr().out


@pytest.mark.parametrize("kernel,ms,products", [
    ("fwd", 2.0, 2), ("dq", 3.0, 3), ("dkv", 4.0, 4)])
def test_each_kernels_share_under_the_window(capsys, kernel, ms, products):
    need = parts.flash_kernel_required(M2_CFG, SLIDING, kernel, 1, 16384)
    assert need[0] == 3 * products * 2.0 * 32 * 128 * 16_253_440
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read(f"flash_swa_{kernel}_roofline", _m2_run())
    assert math.isclose(got, 100.0 * least / (ms * MS))
    assert f"flash_swa_{kernel}_roofline:" in capsys.readouterr().out


def test_swa_experts_roofline_counts_the_held_experts(capsys):
    from benchmarks.harness import qwen3_next_parts as moe_parts

    ops, nbytes, rows = moe_parts.experts_train_required(M2_CFG, 1, 16384)
    least, _ = flops.least_seconds(ops, nbytes, PEAK)
    got = _read("swa_experts_roofline", _m2_run())
    assert math.isclose(got, 100.0 * least / (5.0 * MS))
    assert "16384 expected assignments a layer" in capsys.readouterr().out


def test_a_kind_is_matched_whole_and_a_share_reads_its_own_kind_only():
    """``hvd_attn`` alone (another decoder's attention) is neither kind, and
    a full layer's kernels are not in the window layers' share."""
    other = [(n, p.replace("hvd_attn_window/", "").replace(
        "hvd_attn_full/", ""), a, b) for n, p, a, b in M2_STEP]
    run = _m2_run(other)
    for metric in ("attn_window_ms", "attn_full_ms", "flash_swa_roofline",
                   *KERNEL_SHARES, "flash_full_roofline"):
        assert _read(metric, run) is None, metric
    assert math.isclose(_read("flash_ms", run), 36.0)
    only_full = [s for s in M2_STEP if "hvd_attn_window" not in s[1]]
    run = _m2_run(only_full)
    assert _read("flash_swa_roofline", run) is None
    assert _read("flash_full_roofline", run) is not None


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_a_mellum2_reader_reads_none_where_there_is_nothing_to_read(
        metric, step):
    """The parent of this PR (no such scope, no such configuration key) and
    a cell of another configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    assert _read(metric, run) is None            # GPT-2's keys: no kinds
    run.cell.cfg, run.cell.mix = M2_CFG, M2_MIX
    if step == "conv" or metric != "swa_experts_roofline":
        assert _read(metric, run) is None        # no op under the scope


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_m2_root(tmp_path_factory):
    return benchmark_tiny_mellum2.make(
        str(tmp_path_factory.mktemp("bench")))


def test_tiny_mellum2_cell_runs_end_to_end(tiny_m2_root, world, capsys):
    """Ids from the generator through ``ShardedLoader``, three window layers
    and a full one through the flash kernels under their masks, each with
    its rotary table, the routed experts (held 2..5 of 8) under the load
    bound, through ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_m2_root, "tiny-mellum2", 1)
    _well_formed(result, "tiny-mellum2", 1)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out = capsys.readouterr().out
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap",
                 "update_norm_gap", "final_loss", "nonfinite_losses"):
        assert f"check: {name} = " in out and "limit" in out


def _float8_program(step):
    """A timed path whose parameters are rounded to float8 before every
    step: the lower precision in the program's place."""
    q = common.operand_rounding("fp8")

    def broken(state, x, y):
        import jax

        return step(state._replace(params=jax.tree_util.tree_map(
            lambda p: q(p) if p.ndim > 1 else p, state.params)), x, y)
    return broken


def test_tiny_mellum2_cell_in_float8_is_not_correct(tiny_m2_root, world,
                                                    capsys):
    result = _run_cell(tiny_m2_root, "tiny-mellum2", 1,
                       break_step=_float8_program)
    assert result["correct"] is False
    assert any("OVER" in line for line in
               capsys.readouterr().out.splitlines()
               if line.startswith("check: "))


def test_tiny_mellum2_adds_files_and_entries_and_edits_none(tiny_m2_root,
                                                            tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_m2_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/mellum2_tiny.json", "configs/mellum2_tiny.py",
                     "traffic/seq64-b2-m2.json"}


# -- the cell in ``BENCHMARK.json`` -----------------------------------------------
# (which accepted readers list it is ``test_benchmark_lists.py``'s)


def test_what_the_new_cell_reports():
    spec = Spec(benchmark_tiny.REPO)
    mine = spec.cell(CELL)
    assert (mine.config, mine.traffic, mine.chips) == (
        "mellum2_12b_a2p5b", "seq16k-b1-m2", 1)
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert {*LISTED, *NEW_READERS} <= set(mine.per_layer)
    limits = mine.adapter.limits(mine.cfg, mine.mix)
    assert math.isclose(limits["final_loss"], math.log(12288) + 2.0)
    # the model the adapter builds is the configuration's
    model = mine.adapter.program(mine.cfg, mine.mix)["model"]
    assert (model.num_layers, model.num_experts, model.router_experts,
            model.first_expert, model.num_experts_per_tok,
            model.moe_intermediate_size) == (4, 8, 64, 0, 8, 896)
    assert model.kinds() == (SLIDING, SLIDING, SLIDING, FULL)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.sliding_window, model.vocab_size) == (
        2304, 32, 4, 128, 1024, 12288)
    assert (model.rope_theta, model.yarn_factor,
            model.yarn_original_positions, model.yarn_beta_fast,
            model.yarn_beta_slow, model.yarn_attention_factor) == (
        5e5, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert (model.moe_group_rows, model.moe_capacity_factor,
            model.q_init_std) == (2048, 1.0, mine.cfg[
                "q_proj_initializer_range"])
    assert model.remat and model.dtype == jnp.bfloat16
    # the sample the eager init runs on is short
    assert mine.adapter.program(mine.cfg, mine.mix)["sample"].shape \
        == (1, 1024)
