"""ISSUE 45's benchmark tests: the configuration ``lfm2_24b_a2b``, the
traffic ``seq8k-b2``, the cell ``lfm2-8k-b2`` and its eight readers.

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  Which accepted
cells list which metric follows ``BENCHMARK.json`` in
``test_benchmark_lists.py`` (PR 40); this configuration's cell is held here,
**by name and by rule, not by its place in a list**: the next PR appends
after it."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
import benchmark_tiny_lfm2
from benchmarks.configs import lfm2_24b_a2b as adapter
from benchmarks.harness import check, flops, trace
from benchmarks.harness import lfm2_parts as parts
from benchmarks.harness.spec import Spec
from benchmarks.references import common, lfm2
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
# a Mosaic call's line of a hand-built trace, and the timed path with its
# parameters rounded to float8, as PR 41's file has them
from test_benchmark_nemotron_h import _float8_program, _kernel
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_part_scopes import _fusion
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

CELL, CONFIG, TRAFFIC = "lfm2-8k-b2", "lfm2_24b_a2b", "seq8k-b2"
PUBLISHED = benchmark_tiny_lfm2.PUBLISHED
SCONV_LAYER = "mixers: models/lfm2 gated short convolution"
#: {a new reader: (unit, its layer as PERF.md section 3 has it)}
NEW_READERS = {
    "sconv_ms": ("ms", SCONV_LAYER),
    "sconv_proj_ms": ("ms", SCONV_LAYER),
    "sconv_gate_ms": ("ms", SCONV_LAYER),
    "sconv_gate_roofline": ("%", SCONV_LAYER),
    "sconv_recompute_ms": ("ms", "model step on the device"),
    "dense_mlp_ms": ("ms", "model step on the device"),
    "sconv_experts_roofline": ("%", "parallel/moe routed experts"),
    "flash_h64_gqa_roofline": ("%", "kernels: ops/flash_attention"),
}
#: the accepted readers that list the new cell too (they go by scope, by
#: kernel name or by JAX's mark, and find their ops here)
LISTED = ["fwd_ms", "bwd_ms", "unscoped_ms", "flash_ms", "flash_fwd_ms",
          "flash_dq_ms", "flash_dkv_ms", "flash_layout_ms", "attn_proj_ms",
          "head_ms", "loss_ms", "grad_pack_ms", "moe_ms", "moe_route_ms",
          "moe_tiles", "recompute_ms", "recompute_moe_ms"]
#: what goes by another configuration's scopes or keys and stays off it
NOT_LISTED = ["recompute_mixer_ms", "flash_roofline", "optimizer_ms",
              "gdn_ms", "mla_ms", "ssm_ms", "moe_experts_roofline",
              "mla_experts_roofline", "swa_experts_roofline",
              "relu2_experts_roofline", "flash_gqa_roofline",
              "flash_nope_roofline", "step_ms_p95"]


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


def _cfg():
    return _json("benchmarks", "configs", CONFIG + ".json")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


#: the catalog row's ``config`` (model-configs guide), written out so that
#: the test holds where the guide is not installed; the test below holds
#: this copy to the guide's where it is
SOURCE_CONFIG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PUBLISHED,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
REDUCED = {"num_hidden_layers": (40, (7, 5)), "num_dense_layers": (2, (1,)),
           "num_experts": (64, (8,)), "vocab_size": (65536, (8192,))}


# -- parameters and required operations, one chip's share, by hand --------------


def test_lfm2_parameter_count_by_hand():
    """ISSUE 45's table, part by part."""
    cfg = _cfg()
    d = 2048
    conv = d * 3 * d + 3 * d + d * d
    attention = d * d + 2 * d * 512 + d * d + 2 * 64
    dense = 3 * d * 11776
    experts = d * 64 + 8 * 3 * d * 1536
    table = 8192 * d + d
    assert (conv, attention, 2 * d, dense, experts, table) == (
        16_783_360, 10_485_888, 4_096, 72_351_744, 75_628_544, 16_779_264)
    assert parts.sconv_matmul_params(cfg) == conv - 3 * d
    assert parts.attention_matmul_params(cfg) == attention - 2 * 64
    dense_conv, expert_conv, expert_attention = (
        conv + 2 * d + dense, conv + 2 * d + experts,
        attention + 2 * d + experts)
    assert (dense_conv, expert_conv, expert_attention) == (
        89_139_200, 92_416_000, 86_118_528)
    seven = dense_conv + 2 * expert_attention + 4 * expert_conv + table
    five = dense_conv + expert_attention + 3 * expert_conv + table
    assert (seven, five) == (647_819_520, 469_284_992)
    assert parts.parameters(dict(cfg, num_hidden_layers=7)) == seven
    assert parts.parameters(dict(cfg, num_hidden_layers=5)) == five
    assert parts.parameters(cfg) == cfg["deployment_parameters"]
    assert parts.layer_counts(dict(cfg, num_hidden_layers=7)) == (5, 2, 1, 6)
    assert parts.layer_counts(dict(cfg, num_hidden_layers=5)) == (4, 1, 1, 4)
    # uncut: the name's 24 B
    uncut = dict(cfg, first_layer=0, **{k: v[0] for k, v in REDUCED.items()})
    assert parts.layer_counts(uncut) == (30, 10, 2, 38)
    assert parts.parameters(uncut) == 23_843_659_008 == (
        30 * conv + 10 * attention + 40 * 2 * d + 2 * dense
        + 38 * (d * 64 + 64 * 3 * d * 1536) + 65536 * d + d)


def test_lfm2_train_flops_per_token_by_hand():
    """What ISSUE 45's Motivation counts, forward, per token at 8192: five
    convolution operators of 33.55 M, attention's projections 41.9 M and
    67.1 M over the causal pairs, the dense SwiGLU 144.7 M, six expert
    parts of 9.70 M (the router and 0.5 held picks), the head 33.6 M:
    513.3 M, 1539.8 MFLOP a token for training."""
    cfg = dict(_cfg(), num_hidden_layers=7)
    d, seq = 2048, 8192
    conv = 2 * (d * 3 * d + d * d)
    assert conv == 33_554_432
    projections = 2 * (2 * d * d + 2 * d * 512)
    scores = 2 * 2 * 32 * 64 * (seq * (seq + 1) // 2) / seq
    assert (projections, scores) == (20_971_520, 33_558_528)
    dense = 2 * 3 * d * 11776
    assert dense == 144_703_488
    experts = 2 * (d * 64 + 0.5 * 3 * d * 1536)
    assert experts == 9_699_328
    head = 2 * d * 8192
    forward = 5 * conv + 2 * (projections + scores) + dense + 6 * experts \
        + head
    assert math.isclose(parts.forward_flops_per_token(cfg, seq), forward)
    assert 513.2e6 < forward < 513.4e6
    assert 1539.8e6 < 3 * forward < 1539.9e6
    mix = _json("benchmarks", "traffic", TRAFFIC + ".json")
    assert math.isclose(adapter.flops_per_item(cfg, mix), 3 * forward)
    # 25.2 TFLOP a step of 16 384 tokens; the convolution operators are the
    # largest part, then the dense layer, attention, the experts, the head
    assert 25.2e12 < 3 * forward * 2 * seq < 25.3e12
    shares = [5 * conv / forward, dense / forward,
              2 * (projections + scores) / forward, 6 * experts / forward,
              head / forward]
    assert [round(100 * s, 1) for s in shares] == [32.7, 28.2, 21.2, 11.3,
                                                   6.5]
    five = dict(cfg, num_hidden_layers=5)
    assert math.isclose(
        parts.forward_flops_per_token(five, seq),
        4 * conv + projections + scores + dense + 4 * experts + head)


def test_lfm2_gate_expert_and_flash_requirements_by_hand():
    cfg = dict(_cfg(), num_hidden_layers=7)
    tensor = 2 * 8192 * 2048
    ops, nbytes = parts.sconv_gate_train_required(cfg, 2, 8192)
    # forward B, C, x in and y out; backward dy, B, x, C in, dB, dC, dx out:
    # eleven tensors of 67 MB, five layers; 8 operations a channel a pass
    assert math.isclose(nbytes, 5 * 11 * tensor * 2)
    assert math.isclose(ops, 5 * 3 * tensor * 8)
    assert 4 * tensor * 2 == 268_435_456       # ISSUE 45: forward 268 MB
    least, bound = flops.least_seconds(ops, nbytes, PEAK)
    assert bound == "memory" and 4.4e-3 < least < 4.6e-3
    ops, nbytes, rows = parts.experts_train_required(cfg, 2, 8192)
    assert rows == 2 * 8192 * 4 * 8 / 64 == 8192
    expert = 3 * 2048 * 1536
    assert math.isclose(ops, 6 * 3 * 2 * 8192 * expert)
    weights = 8 * expert
    rows_bytes = 8192 * (2 * 2048 + 3 * 1536) * 2
    assert math.isclose(nbytes, 6 * (2 * (weights * 2 + rows_bytes)
                                     + weights * 4 + rows_bytes))
    # two attention layers' seven products over the causal half
    ops, nbytes = parts.flash_train_required(cfg, 2, 8192)
    assert math.isclose(ops, 2 * 7 * 2.0 * 2 * 32 * 8192 * 8192 * 64 / 2)
    assert (ops, nbytes) == flops.flash_train_required(
        2, 32, 8192, 64, causal=True, layers=2)


# -- the files ---------------------------------------------------------------------


def test_the_copy_of_the_catalog_row_is_the_catalogs():
    row = _catalog_row()
    if row is None:
        pytest.skip("the model-configs guide is not installed here")
    assert row["config"] == SOURCE_CONFIG
    assert row["source_url"] == _cfg()["source"]


@pytest.mark.parametrize("key", sorted(SOURCE_CONFIG))
def test_every_published_key_is_the_catalog_rows(key):
    """Every number of the catalog entry's ``config`` under the same key;
    a key that differs is in ``reduced``, and ``reduced`` names no
    width."""
    cfg = _cfg()
    if key in REDUCED:
        published, held = REDUCED[key]
        assert SOURCE_CONFIG[key] == published == cfg["published"][key]
        assert cfg[key] in held and key in cfg["reduced"]
        assert str(cfg[key]) in cfg["reduced"][key]
    else:
        assert cfg[key] == SOURCE_CONFIG[key]
        assert key not in cfg["reduced"]


def test_lfm2_files_state_the_cut_and_the_traffic_of_its_cell():
    cfg = _cfg()
    bench = _json("BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["source"] == cfg["source"] and "LFM2-24B-A2B" \
        in conf["source"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "num_experts", "vocab_size"] \
        == list(cfg["reduced"]) == list(cfg["published"])
    assert not [k for k in conf["reduced"] if k.endswith(("_dim", "_rank"))
                or "size" in k.replace("vocab_size", "")]
    # the cut: published layers 1 to 7 (or 1 to 5, by the reading the file
    # states), one dense layer, 8 experts of an 8-chip share, an eighth of
    # the vocabulary
    assert cfg["first_layer"] == 1
    assert list(lfm2.kinds(cfg)) == PUBLISHED[1:1 + cfg["num_hidden_layers"]]
    assert lfm2.kinds(cfg)[0] == "conv" and lfm2.is_dense(cfg, 0) \
        and not lfm2.is_dense(cfg, 1)
    assert "THE READING" in cfg["reduced"]["num_hidden_layers"]
    assert (cfg["num_experts"], cfg["router_num_experts"],
            cfg["first_expert"]) == (8, 64, 0)
    assert (cfg["moe_group_rows"], cfg["moe_capacity_factor"]) == (4096, 1.0)
    assert math.ceil(1.0 * 4096 * 4 / 64) == 256
    assert (cfg["qk_norm_init"], cfg["initializer_range"],
            cfg["learning_rate"], cfg["optimizer"]) == (2.0, 0.02, 1e-4,
                                                        "adam")
    for key in ("tied_head", "in_proj_order", "head_dim", "training_recipe",
                "loss", "selection_bias", "weights", "expert_capacity",
                "sequence_length"):
        assert key in cfg["assumed"], key
    assert "8 chips" in cfg["deployment"]
    assert not [k for k in cfg if k.endswith("_initializer_range")]
    mix = _json("benchmarks", "traffic", TRAFFIC + ".json")
    assert mix == {
        "rows_per_chip": 2, "dataset_rows_per_chip": 256,
        "arrays": [{"name": "ids", "shape": [8192], "dtype": "int32",
                    "low": 0, "high": "vocab_size"}],
        "items_per_row": 8192, "rate_metric": "tokens_per_s_chip"}
    # no row comes twice in a window of 60 steps and the three checked ones
    assert mix["dataset_rows_per_chip"] >= (60 + 3) * mix["rows_per_chip"]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(benchmark_tiny.REPO, "benchmarks", "references",
                        "lfm2.py")
    with open(path) as fh:
        text = fh.read()
    code = [line for line in text.splitlines()
            if line.startswith(("import ", "from "))]
    assert code and not any("horovod_tpu" in line for line in code)
    assert "horovod_tpu" not in text


# -- the control --------------------------------------------------------------

TOY = benchmark_tiny_lfm2.LFM2_TINY
#: The cell's limits are read on the chip at the cell's size.  The toy is
#: float32, five layers and 64 tokens: a sound program reads 1e-6 and its
#: float8 control far more, so the toy holds the control to a limit between
#: those.
TOY_LIMITS = dict(adapter.LIMITS, grad_sketch_gap=0.02)


def _toy_batch(seed, rows=2, length=64):
    return (np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], (rows, length)).astype(np.int32),)


@pytest.mark.parametrize("seed", [1, 2])
def test_float8_control_is_not_correct_lfm2(seed):
    ref = {"init": lambda s: lfm2.seeded_weights(TOY, seed),
           "loss": lambda p: lfm2.loss_fn(TOY, p), "optimizer": "adam",
           "lr": 1e-4}
    batches = [_toy_batch(seed * 10 + i) for i in range(3)]
    numbers = check.first_steps_numbers(
        common.follow(ref, 0, batches, 2, "fp8"),
        common.follow(ref, 0, batches, 2))
    correct, lines = check.verdict(
        numbers, {k: TOY_LIMITS[k] for k in numbers})
    assert not correct, lines
    assert numbers["grad_sketch_gap"] > 2 * TOY_LIMITS["grad_sketch_gap"]


def test_the_cells_limits_are_set_and_the_sketch_is_the_one_that_fails():
    """Every compared number has a limit, and ``grad_sketch_gap`` lies
    between the sound side's largest and the control's smallest as
    ``calibrate.py`` read them on the chip (the module's comments and
    PERF.md section 2 carry the readings)."""
    cfg, mix = _cfg(), _json("benchmarks", "traffic", TRAFFIC + ".json")
    limits = adapter.limits(cfg, mix)
    assert set(limits) == {
        "loss_gap", "grad_norm_gap", "grad_sketch_gap", "update_norm_gap",
        "nonfinite_losses", "batch_shards_missing",
        "state_leaves_not_replicated", "final_loss"}
    assert math.isclose(limits["final_loss"], math.log(8192) + 2.0)
    assert 0 < limits["grad_sketch_gap"] < 1
    assert limits["update_norm_gap"] < 1.0
    assert limits["nonfinite_losses"] == 0.0


# -- the eight readers on a hand-built trace -------------------------------------

L2_CFG = {k: v for k, v in _cfg().items()
          if k not in ("reduced", "assumed", "published", "memory_gb")} \
    | {"num_hidden_layers": 7}
L2_MIX = {"rows_per_chip": 2, "arrays": [{"shape": [8192]}]}
NF = "jit(s)/jvp(hvd_forward)/Lfm2/"
NB = "jit(s)/transpose(jvp(hvd_forward))/Lfm2/jvp(hvd_forward)/Lfm2/" \
     "checkpoint/"
C0 = "layers_0/conv/hvd_sconv/"
D0 = "layers_0/feed_forward/hvd_dense_mlp/"
A1 = "layers_1/self_attn/hvd_attn/"
E1 = "layers_1/feed_forward/hvd_moe/"


#: one step of 60 ms: (HLO text, tf_op, start ms, end ms)
L2_STEP = [
    _fusion(1, NF + C0 + "hvd_sconv_in/in_proj/dot_general:", 0, 2),
    _fusion(2, NF + C0 + "hvd_sconv_conv/mul:", 2, 2.5),
    _fusion(3, NF + C0 + "hvd_sconv_conv/add:", 2.5, 3),
    _fusion(4, NF + C0 + "hvd_sconv_out/out_proj/dot_general:", 3, 4),
    _fusion(5, NF + D0 + "gate_proj/dot_general:", 4, 7),
    _fusion(6, NF + A1 + "hvd_attn_qkv/q_proj/dot_general:", 7, 8),
    _fusion(7, NF + A1 + "hvd_flash_layout/transpose:", 8, 8.5),
    _kernel(8, "hvd_flash_fwd", NF + A1, 8.5, 11),
    _fusion(9, NF + A1 + "hvd_attn_out/out_proj/dot_general:", 11, 12),
    _fusion(10, NF + E1 + "hvd_moe_route/top_k:", 12, 13),
    ("%while.11 = (s32[]) while(%t)", NF + E1 + "while:", 13, 15),
    _fusion(12, NF + E1 + "while/body/hvd_moe_experts/dot_general:", 13, 15),
    _fusion(13, NF + "hvd_head/einsum/dot_general:", 15, 18),
    _fusion(14, NF[:-5] + "hvd_loss/reduce_sum:", 18, 18.5),
    # the expert part's recompute and backward
    _fusion(15, NB + "rematted_computation/" + E1
            + "hvd_moe_route/dot_general:", 18.5, 19.5),
    _fusion(16, NB + E1 + "while/body/hvd_moe_experts/dot_general:", 19.5,
            23.5),
    # the attention operator's
    _fusion(17, NB + "rematted_computation/" + A1
            + "hvd_attn_qkv/q_proj/dot_general:", 23.5, 24.5),
    _kernel(18, "hvd_flash_dq", NB + A1, 24.5, 28),
    _kernel(19, "hvd_flash_dkv", NB + A1, 28, 32),
    # the dense part's: its products again (marked), then the transposes
    _fusion(20, NB + "rematted_computation/" + D0 + "gate_proj/dot_general:",
            32, 35),
    _fusion(21, NB + D0 + "down_proj/transpose:", 35, 41),
    # the convolution operator's: in_proj, the gates and taps again
    # (marked), then the transposes of all three parts
    _fusion(22, NB + "rematted_computation/" + C0
            + "hvd_sconv_in/in_proj/dot_general:", 41, 43),
    _fusion(23, NB + "rematted_computation/" + C0 + "hvd_sconv_conv/mul:",
            43, 44),
    _fusion(24, NB + C0 + "hvd_sconv_out/out_proj/transpose:", 44, 46),
    _fusion(25, NB + C0 + "hvd_sconv_conv/mul:", 46, 48),
    _fusion(26, NB + C0 + "hvd_sconv_in/in_proj/transpose:", 48, 52),
    ("%fusion.27 = f32[10] fusion(%g)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/pack/concatenate:", 52, 52.5),
    ("%fusion.28 = f32[10] fusion(%p)", "jit(s)/hvd_optimizer_update/add:",
     52.5, 59.5),
    ("%copy-done.29 = f32[10] copy-done(%c)", "", 59.5, 60),
]


def _l2_run(step=L2_STEP, cfg=L2_CFG) -> RunRecord:
    ops = [trace.Op(name, (60 * i + a) * MS, (60 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": cfg, "mix": L2_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=60 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 60 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


def test_the_parts_of_hvd_sconv_add_up_to_sconv_ms():
    run = _l2_run()
    # first run: in 2, gates and taps 1, out 1; again: in 2, gates 1; the
    # transposes: out 2, gates 2, in 4
    assert math.isclose(_read("sconv_ms", run), 4.0 + 11.0)
    assert math.isclose(_read("sconv_proj_ms", run), 2 + 1 + 2 + 2 + 4)
    assert math.isclose(_read("sconv_gate_ms", run), 1.0 + 1.0 + 2.0)
    assert math.isclose(
        _read("sconv_ms", run),
        _read("sconv_proj_ms", run) + _read("sconv_gate_ms", run))
    # the recompute's share of it: the marked ops under hvd_sconv alone
    assert math.isclose(_read("sconv_recompute_ms", run), 3.0)
    assert math.isclose(_read("dense_mlp_ms", run), 3.0 + 3.0 + 6.0)
    assert math.isclose(_read("recompute_ms", run), 1.0 + 1.0 + 3.0 + 3.0)
    # the feed-forward parts' second run: the router's and the dense layer's
    assert math.isclose(_read("recompute_moe_ms", run), 1.0 + 3.0)
    # the accepted readers find the kernels and scopes of the other parts
    assert math.isclose(_read("flash_ms", run), 2.5 + 3.5 + 4)
    assert math.isclose(_read("attn_proj_ms", run), 1 + 1 + 1)
    assert math.isclose(_read("flash_layout_ms", run), 0.5)
    assert math.isclose(_read("moe_ms", run), 3.0 + 1.0 + 4.0)
    assert math.isclose(_read("moe_route_ms", run), 2.0)
    assert math.isclose(_read("moe_tiles", run), 1.0)
    assert math.isclose(_read("head_ms", run), 3.0)
    assert math.isclose(_read("loss_ms", run), 0.5)
    assert math.isclose(_read("grad_pack_ms", run), 0.5)


def test_every_reader_the_cell_lists_returns_a_number_on_such_a_trace():
    """A CPU run has no device plane to trace, so what the chip's traced run
    prints is held on the chip (PERF.md section 6); here every reader the
    cell lists that reads the device trace finds its ops in a step shaped
    as this model's (a layer of each operator and of each feed-forward
    kind, the kernels, a marked recompute, the expert loop, head, loss and
    pack)."""
    run = _l2_run()
    cell = Spec(benchmark_tiny.REPO).cell(CELL)
    for name in [*LISTED, *NEW_READERS]:
        assert name in cell.per_layer, name
        value = _read(name, run)
        assert value is not None and value > 0, name


def test_the_three_shares_are_least_time_over_their_ops(capsys):
    run = _l2_run()
    for metric, need, ms in (
            ("sconv_gate_roofline",
             parts.sconv_gate_train_required(L2_CFG, 2, 8192), 4.0),
            ("sconv_experts_roofline",
             parts.experts_train_required(L2_CFG, 2, 8192)[:2], 6.0),
            ("flash_h64_gqa_roofline",
             parts.flash_train_required(L2_CFG, 2, 8192), 10.0)):
        least, _ = flops.least_seconds(*need, PEAK)
        got = _read(metric, run)
        assert math.isclose(got, 100 * least / (ms * MS)), metric
        assert f"{metric}: " in capsys.readouterr().out
    # a share over 100 would say the bytes are counted too high: at the
    # bandwidth's own pace the gates' share reads 100
    least, _ = flops.least_seconds(
        *parts.sconv_gate_train_required(L2_CFG, 2, 8192), PEAK)
    paced = [_fusion(1, NF + C0 + "hvd_sconv_conv/mul:", 0, least / MS)]
    assert math.isclose(_read("sconv_gate_roofline", _l2_run(paced)), 100.0)


def test_a_scope_is_matched_whole():
    """``hvd_sconv_in`` never answers for ``hvd_sconv``'s other parts, nor
    a longer name for ``hvd_sconv_conv``."""
    step = [_fusion(1, NF + C0 + "hvd_sconv_convolve/mul:", 0, 2),
            _fusion(2, NF + "layers_0/conv/hvd_sconv_other/mul:", 2, 4),
            _fusion(3, NF + "layers_0/feed_forward/hvd_dense_mlp_x/mul:",
                    4, 6)]
    run = _l2_run(step)
    for metric in NEW_READERS:
        if metric == "sconv_ms":
            assert math.isclose(_read(metric, run), 2.0)
        else:
            assert _read(metric, run) is None, metric


@pytest.mark.parametrize("metric", sorted(NEW_READERS))
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_an_lfm2_reader_reads_none_where_there_is_nothing_to_read(metric,
                                                                  step):
    """The parent of this PR (no such scope, no such configuration key) and
    a cell of another configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    assert _read(metric, run) is None            # GPT-2's keys, no scope
    run.cell.cfg, run.cell.mix = L2_CFG, L2_MIX
    if metric == "flash_h64_gqa_roofline" and step == "gpt":
        # the kernels go by their names, whatever model calls them
        assert _read(metric, run) > 0
    else:
        assert _read(metric, run) is None        # no op under the scope


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_l2_root(tmp_path_factory):
    return benchmark_tiny_lfm2.make(str(tmp_path_factory.mktemp("bench")))


def test_tiny_lfm2_cell_runs_end_to_end(tiny_l2_root, world, capsys):
    """Ids from the generator through ``ShardedLoader``, a dense convolution
    layer, an attention layer through the flash kernels and three
    convolution layers with experts (held 2..5 of 8) under the load bound,
    the tied head, through ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_l2_root, "tiny-lfm2", 1)
    _well_formed(result, "tiny-lfm2", 1)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out = capsys.readouterr().out
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap",
                 "update_norm_gap", "final_loss", "nonfinite_losses"):
        assert f"check: {name} = " in out and "limit" in out


def test_tiny_lfm2_cell_in_float8_is_not_correct(tiny_l2_root, world, capsys):
    result = _run_cell(tiny_l2_root, "tiny-lfm2", 1,
                       break_step=_float8_program)
    assert result["correct"] is False
    assert any("OVER" in line for line in
               capsys.readouterr().out.splitlines()
               if line.startswith("check: "))


def test_tiny_lfm2_adds_files_and_entries_and_edits_none(tiny_l2_root,
                                                         tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_l2_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/lfm2_tiny.json", "configs/lfm2_tiny.py",
                     "traffic/seq64-b2-l2.json"}


# -- the cell in ``BENCHMARK.json`` -----------------------------------------------


def test_the_traffic_and_the_cells_entries_by_the_lists_rules():
    """``test_benchmark_lists.py``'s rules, for the configuration that file
    leaves to this PR: a reader lists the cell where the configuration has
    the scope, the kernel or the key it goes by, and every list that has
    the cell reports the end-to-end metric its reader moves."""
    bench = _json("BENCHMARK.json")
    rates = {m["name"]: m for m in bench["end_to_end"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.count(CELL) == 1
    mix = _json("benchmarks", "traffic", TRAFFIC + ".json")
    assert CELL in rates[mix["rate_metric"]]["workloads"]
    assert CELL not in rates["step_ms_p95"]["workloads"]
    assert CELL not in rates["images_per_s_chip"]["workloads"]
    listing = {name for name, m in entries.items()
               if CELL in m.get("workloads", [CELL])}
    every = {name for name, m in entries.items() if "workloads" not in m}
    assert listing == every | set(LISTED) | set(NEW_READERS)
    for name in listing:
        moved = rates[entries[name]["moves"]]
        assert CELL in moved.get("workloads", cells), name
    # a new reader is this cell's alone, by name and with a file
    for name, (unit, layer) in NEW_READERS.items():
        m = entries[name]
        assert m == {"name": name, "unit": unit,
                     "better": "higher" if unit == "%" else "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "mfu", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            benchmark_tiny.REPO, "benchmarks", "layer_metrics",
            name + ".py"))
    # eleven cells or more, one on four chips
    assert len(cells) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_what_the_new_cell_reports():
    spec = Spec(benchmark_tiny.REPO)
    mine = spec.cell(CELL)
    assert (mine.config, mine.traffic, mine.chips) == (CONFIG, TRAFFIC, 1)
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert {*LISTED, *NEW_READERS} <= set(mine.per_layer)
    assert not set(NOT_LISTED) & {*mine.per_layer, *mine.end_to_end}
    bench = _json("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "33%" in entry["why"]
    limits = mine.adapter.limits(mine.cfg, mine.mix)
    assert math.isclose(limits["final_loss"], math.log(8192) + 2.0)
    # the model the adapter builds is the configuration's
    model = mine.adapter.program(mine.cfg, mine.mix)["model"]
    assert list(model.kinds()) == PUBLISHED[1:1 + mine.cfg[
        "num_hidden_layers"]]
    assert (model.hidden_size, model.vocab_size, model.intermediate_size,
            model.conv_taps, model.num_dense_layers) == (
        2048, 8192, 11776, 3, 1)
    assert (model.num_heads, model.num_kv_heads, model.head_dim,
            model.rope_theta, model.qk_norm_init) == (32, 8, 64, 1e6, 2.0)
    assert (model.num_experts, model.router_experts, model.first_expert,
            model.num_experts_per_tok, model.moe_intermediate_size,
            model.routed_scaling_factor) == (8, 64, 0, 4, 1536, 1.0)
    assert (model.moe_group_rows, model.moe_capacity_factor,
            model.norm_eps) == (4096, 1.0, 1e-5)
    assert model.remat and model.dtype == jnp.bfloat16
    # the sample the eager init runs on is short
    assert mine.adapter.program(mine.cfg, mine.mix)["sample"].shape \
        == (1, 1024)
