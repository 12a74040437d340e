"""ISSUE 41's benchmark tests: the configuration ``nemotron3_nano_30b_a3b``,
the traffic ``seq8k-b1-n3``, the cell ``nemotron3-8k`` and its seven
readers.

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  Which accepted
cells list which metric follows ``BENCHMARK.json`` in
``test_benchmark_lists.py`` (PR 40); this configuration's cell is held
here."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
import benchmark_tiny_nemotron_h
from benchmarks.configs import nemotron3_nano_30b_a3b as adapter
from benchmarks.harness import check, flops, trace
from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness.spec import Spec
from benchmarks.references import common, nemotron_h
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_part_scopes import _fusion
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

CELL = "nemotron3-8k"
PUBLISHED = benchmark_tiny_nemotron_h.PUBLISHED
NEW_READERS = ["ssm_ms", "ssm_proj_ms", "ssm_conv_ms", "ssm_scan_ms",
               "ssm_recompute_ms", "ssm_scan_roofline",
               "relu2_experts_roofline", "flash_nope_roofline"]
#: the accepted readers that list the new cell too (they go by scope, by
#: kernel name or by JAX's mark, and find their ops here)
LISTED = ["fwd_ms", "bwd_ms", "unscoped_ms", "flash_ms", "flash_fwd_ms",
          "flash_dq_ms", "flash_dkv_ms", "flash_layout_ms", "attn_proj_ms",
          "head_ms", "loss_ms", "grad_pack_ms", "moe_ms", "moe_route_ms",
          "moe_tiles", "recompute_ms", "recompute_moe_ms"]
#: what goes by another configuration's scopes or keys and stays off it
NOT_LISTED = ["recompute_mixer_ms", "flash_roofline", "optimizer_ms",
              "gdn_ms", "mla_ms", "moe_experts_roofline",
              "mla_experts_roofline", "swa_experts_roofline"]


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


def _cfg():
    return _json("benchmarks", "configs", "nemotron3_nano_30b_a3b.json")


# -- parameters and required operations, one chip's share, by hand --------------


def test_nemotron_h_parameter_count_by_hand():
    cfg = _cfg()
    d = 2688
    in_proj = d * (2 * 4096 + 2 * 8 * 128 + 64)
    mamba = in_proj + (6144 * 4 + 6144) + 4096 * d + 3 * 64 + 4096 + d
    assert (in_proj, mamba) == (27_697_152, 38_744_896)
    assert parts.mamba_matmul_params(cfg) == in_proj + 6144 * 4 + 4096 * d
    experts = 8 * 2 * d * 1856 + 2 * d * 3712 + d * 128 + d
    assert experts == 100_125_312
    attention = d * 4096 + 2 * d * 256 + 4096 * d + d
    assert attention == 23_399_040 == parts.attention_matmul_params(cfg) + d
    table = 2 * 16384 * d + d
    assert table == 88_083_072
    by_kind = {"M": mamba, "E": experts, "*": attention}
    for blocks, total in ((9, 666_962_944), (7, 528_092_736)):
        held = dict(cfg, num_hidden_layers=blocks)
        assert parts.parameters(held) == total == table + sum(
            by_kind[k] for k in PUBLISHED[:blocks])
    assert parts.parameters(cfg) == cfg["deployment_parameters"]
    assert parts.block_counts(dict(cfg, num_hidden_layers=9)) == (4, 4, 1)
    assert parts.block_counts(dict(cfg, num_hidden_layers=7)) == (3, 3, 1)


def test_nemotron_h_train_flops_per_token_by_hand():
    """What ISSUE 41's Motivation counts, forward, per token at 8192: a
    state-space block's projections 77.4 M, its convolution 0.05 M, its
    recurrence 3.1 M (three P x N products a head); attention's
    projections 46.8 M and 67.1 M over the causal pairs; an expert block's
    shared expert 39.9 M, router 0.7 M and 0.375 held picks of 20.0 M; the
    head 88.1 M."""
    cfg = dict(_cfg(), num_hidden_layers=9)
    d, seq = 2688, 8192
    mamba = 2 * (d * 10304 + 4096 * d) + 2 * 4 * 6144 + 6 * 64 * 64 * 128
    assert mamba == 77_414_400 + 49_152 + 3_145_728
    attention = 2 * (2 * d * 4096 + 2 * d * 256) \
        + 2 * 2 * 32 * 128 * (seq * (seq + 1) // 2) / seq
    assert math.isclose(attention, 46_792_704 + 67_117_056)
    experts = 2 * (2 * d * 3712 + d * 128 + 0.375 * 2 * d * 1856)
    assert math.isclose(experts, 39_911_424 + 688_128 + 7_483_392)
    head = 2 * d * 16384
    forward = 4 * mamba + attention + 4 * experts + head
    assert math.isclose(parts.forward_flops_per_token(cfg, seq), forward)
    assert 716.7e6 < forward < 716.8e6
    mix = _json("benchmarks", "traffic", "seq8k-b1-n3.json")
    assert math.isclose(adapter.flops_per_item(cfg, mix), 3 * forward)
    # 17.6 TFLOP a step; the state-space blocks are the largest part
    assert 17.6e12 < 3 * forward * seq < 17.7e12
    assert 0.44 < 4 * mamba / forward < 0.46
    seven = dict(cfg, num_hidden_layers=7)
    assert math.isclose(parts.forward_flops_per_token(seven, seq),
                        3 * mamba + attention + 3 * experts + head)


def test_nemotron_h_scan_and_expert_requirements_by_hand():
    cfg = dict(_cfg(), num_hidden_layers=9)
    ops, nbytes = parts.scan_train_required(cfg, 1, 8192)
    # three passes of the recurrence's 3.1 M operations a token, four blocks
    assert math.isclose(ops, 4 * 3 * 8192 * 3_145_728)
    # x, B, C in and y out in bf16, dt in float32; three passes
    tensors = 8192 * ((6144 + 4096) * 2 + 64 * 4)
    assert math.isclose(nbytes, 4 * 3 * tensors)
    least, bound = flops.least_seconds(ops, nbytes, PEAK)
    assert bound == "memory" and 2.4e-3 < least < 2.6e-3
    ops, nbytes, rows = parts.experts_train_required(cfg, 1, 8192)
    assert rows == 8192 * 6 * 8 / 128 == 3072
    expert = 2 * 2688 * 1856
    assert math.isclose(ops, 4 * 3 * 2 * 3072 * expert)
    weights = 8 * expert
    rows_bytes = 3072 * (2 * 2688 + 2 * 1856) * 2
    assert math.isclose(nbytes, 4 * (2 * (weights * 2 + rows_bytes)
                                     + weights * 4 + rows_bytes))


def test_nemotron_h_files_state_the_cut_and_the_traffic_of_its_cell():
    cfg = _cfg()
    bench = _json("BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "nemotron3_nano_30b_a3b")
    assert conf["source"] == cfg["source"] and "NVIDIA-Nemotron-3-Nano" \
        in conf["source"]
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"] == list(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    # every published width is as the source has it
    widths = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856, "intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "norm_eps": 1e-05, "layer_norm_epsilon": 1e-05,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 0.0001, "mlp_hidden_act": "relu2",
        "hybrid_override_pattern": PUBLISHED, "model_type": "nemotron_h",
        "use_conv_bias": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "router_num_experts": 128}
    assert {k: cfg[k] for k in widths} == widths
    # the cut: nine blocks or seven (by the reading the file states), 8
    # experts of a 16-chip share, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] in (9, 7)
    assert str(cfg["num_hidden_layers"]) in cfg["reduced"][
        "num_hidden_layers"]
    assert (cfg["n_routed_experts"], cfg["first_expert"],
            cfg["vocab_size"]) == (8, 0, 131072 // 8)
    assert (cfg["moe_group_rows"], cfg["moe_capacity_factor"]) == (4096,
                                                                   1.25)
    assert math.ceil(1.25 * 4096 * 6 / 128) == 240
    for key in ("training_recipe", "loss", "selection_bias", "no_rotary",
                "sequence_length", "weights", "gated_norm_groups",
                "convolution", "dt_limit", "expert_capacity"):
        assert key in cfg["assumed"], key
    assert "16 chips" in cfg["deployment"]
    mix = _json("benchmarks", "traffic", "seq8k-b1-n3.json")
    assert (mix["rows_per_chip"], mix["items_per_row"],
            mix["rate_metric"]) == (1, 8192, "tokens_per_s_chip")
    assert mix["dataset_rows_per_chip"] >= 128
    assert mix["arrays"] == [{"name": "ids", "shape": [8192],
                              "dtype": "int32", "low": 0,
                              "high": "vocab_size"}]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(benchmark_tiny.REPO, "benchmarks", "references",
                        "nemotron_h.py")
    with open(path) as fh:
        code = [line for line in fh if line.startswith(("import ", "from "))]
    assert code and not any("horovod_tpu" in line for line in code)


# -- the control --------------------------------------------------------------

TOY = benchmark_tiny_nemotron_h.NEMOTRON_H_TINY
#: The cell's limits are read on the chip at the cell's size.  The toy is
#: float32, seven blocks and 64 tokens: a sound program reads 1e-6 and its
#: float8 control far more, so the toy holds the control to a limit between
#: those.
TOY_LIMITS = dict(adapter.LIMITS, grad_sketch_gap=0.02)


def _toy_batch(seed, rows=2, length=64):
    return (np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], (rows, length)).astype(np.int32),)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_nemotron_h(seed):
    ref = {"init": lambda s: nemotron_h.seeded_weights(TOY, seed),
           "loss": lambda p: nemotron_h.loss_fn(TOY, p), "optimizer": "adam",
           "lr": 1e-4}
    batches = [_toy_batch(seed * 10 + i) for i in range(3)]
    numbers = check.first_steps_numbers(
        common.follow(ref, 0, batches, 2, "fp8"),
        common.follow(ref, 0, batches, 2))
    correct, lines = check.verdict(
        numbers, {k: TOY_LIMITS[k] for k in numbers})
    assert not correct, lines
    assert numbers["grad_sketch_gap"] > 2 * TOY_LIMITS["grad_sketch_gap"]


# -- the seven readers on a hand-built trace -------------------------------------

N3_CFG = {k: v for k, v in _cfg().items()
          if not isinstance(v, dict)} | {"num_hidden_layers": 9}
N3_MIX = {"rows_per_chip": 1, "arrays": [{"shape": [8192]}]}
NF = "jit(s)/jvp(hvd_forward)/NemotronH/"
NB = "jit(s)/transpose(jvp(hvd_forward))/NemotronH/jvp(hvd_forward)/" \
     "NemotronH/checkpoint/"
M0 = "layers_0/mixer/hvd_ssm/"
A5 = "layers_5/mixer/hvd_attn/"
E1 = "layers_1/mixer/hvd_moe/"


def _kernel(i, name, path, start, end):
    return (f"%{name}.{i} = bf16[8]" + MOSAIC,
            path + f"jit(_call)/{name}/{name}/pallas_call:", start, end)


#: one step of 60 ms: (HLO text, tf_op, start ms, end ms)
N3_STEP = [
    _fusion(1, NF + M0 + "hvd_ssm_in/in_proj/dot_general:", 0, 2),
    _fusion(2, NF + M0 + "hvd_ssm_conv/add:", 2, 3),
    _fusion(3, NF + M0 + "hvd_ssm_in/softplus:", 3, 3.5),
    ("%while.4 = (s32[]) while(%t)", NF + M0 + "hvd_ssm_scan/while:", 3.5, 6),
    _fusion(5, NF + M0 + "hvd_ssm_scan/while/body/mul:", 4, 5),
    _fusion(6, NF + M0 + "hvd_ssm_scan/dot_general:", 6, 7.5),
    _fusion(7, NF + M0 + "hvd_ssm_out/out_proj/dot_general:", 7.5, 9),
    _fusion(8, NF + E1 + "hvd_moe_route/top_k:", 9, 10),
    ("%while.9 = (s32[]) while(%t)", NF + E1 + "while:", 10, 12),
    _fusion(10, NF + E1 + "while/body/hvd_moe_experts/dot_general:", 10, 12),
    _fusion(11, NF + E1 + "hvd_moe_shared/shared_experts_up_proj/"
            "dot_general:", 12, 13),
    _fusion(12, NF + A5 + "hvd_attn_qkv/q_proj/dot_general:", 13, 14),
    _fusion(13, NF + A5 + "hvd_flash_layout/transpose:", 14, 14.5),
    _kernel(14, "hvd_flash_fwd", NF + A5, 14.5, 18),
    _fusion(15, NF + A5 + "hvd_attn_out/o_proj/dot_general:", 18, 19),
    _fusion(16, NF + "hvd_head/dot_general:", 19, 22),
    _fusion(17, NF[:-10] + "hvd_loss/reduce_sum:", 22, 22.5),
    # the attention block's recompute and backward
    _fusion(18, NB + "rematted_computation/" + A5
            + "hvd_attn_qkv/q_proj/dot_general:", 22.5, 23.5),
    _kernel(19, "hvd_flash_dq", NB + A5, 23.5, 28),
    _kernel(20, "hvd_flash_dkv", NB + A5, 28, 33),
    # the expert block's
    _fusion(21, NB + "rematted_computation/" + E1
            + "hvd_moe_route/dot_general:", 33, 34),
    _fusion(22, NB + E1 + "while/body/hvd_moe_experts/dot_general:", 34, 38),
    # the state-space block's: in_proj and the convolution again (marked),
    # the scan's backward rule (not marked), the transposes
    _fusion(23, NB + "rematted_computation/" + M0
            + "hvd_ssm_in/in_proj/dot_general:", 38, 40),
    _fusion(24, NB + "rematted_computation/" + M0 + "hvd_ssm_conv/add:",
            40, 41),
    _fusion(25, NB + M0 + "hvd_ssm_scan/dot_general:", 41, 49),
    _fusion(26, NB + M0 + "hvd_ssm_out/out_proj/transpose:", 49, 51),
    _fusion(27, NB + M0 + "hvd_ssm_conv/transpose:", 51, 52),
    _fusion(28, NB + M0 + "hvd_ssm_in/in_proj/transpose:", 52, 56),
    ("%fusion.29 = f32[10] fusion(%g)",
     "jit(s)/hvd_grad_allreduce/hvd_bucket_0/pack/concatenate:", 56, 56.5),
    ("%fusion.30 = f32[10] fusion(%p)", "jit(s)/hvd_optimizer_update/add:",
     56.5, 59.5),
    ("%copy-done.31 = f32[10] copy-done(%c)", "", 59.5, 60),
]


def _n3_run(step=N3_STEP, cfg=N3_CFG) -> RunRecord:
    ops = [trace.Op(name, (60 * i + a) * MS, (60 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": cfg, "mix": N3_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=60 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 60 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


def test_the_parts_of_hvd_ssm_add_up_to_ssm_ms():
    run = _n3_run()
    # in 2 + 0.5, conv 1, scan 2.5 + 1.5 (the loop and its body are one
    # interval), out 1.5; again: in 2, conv 1; scan 8, out 2, conv 1, in 4
    assert math.isclose(_read("ssm_ms", run), 9.0 + 18.0)
    assert math.isclose(_read("ssm_proj_ms", run), 2.5 + 1.5 + 2 + 2 + 4)
    assert math.isclose(_read("ssm_conv_ms", run), 1.0 + 1.0 + 1.0)
    assert math.isclose(_read("ssm_scan_ms", run), 4.0 + 8.0)
    assert math.isclose(
        _read("ssm_ms", run), _read("ssm_proj_ms", run)
        + _read("ssm_conv_ms", run) + _read("ssm_scan_ms", run))
    # the recompute's share of it: the marked ops under hvd_ssm alone
    assert math.isclose(_read("ssm_recompute_ms", run), 3.0)
    assert math.isclose(_read("recompute_ms", run), 1.0 + 1.0 + 3.0)
    assert math.isclose(_read("recompute_moe_ms", run), 1.0)
    # the accepted readers find the kernels and scopes of the other blocks
    assert math.isclose(_read("flash_ms", run), 3.5 + 4.5 + 5)
    assert math.isclose(_read("attn_proj_ms", run), 1 + 1 + 1)
    assert math.isclose(_read("flash_layout_ms", run), 0.5)
    assert math.isclose(_read("moe_ms", run), 4.0 + 1.0 + 4.0)
    assert math.isclose(_read("moe_route_ms", run), 2.0)
    assert math.isclose(_read("moe_tiles", run), 1.0)
    assert math.isclose(_read("head_ms", run), 3.0)
    assert math.isclose(_read("loss_ms", run), 0.5)
    assert math.isclose(_read("grad_pack_ms", run), 0.5)


def test_every_reader_the_cell_lists_returns_a_number_on_such_a_trace():
    """A CPU run has no device plane to trace, so what the chip's traced run
    prints is held on the chip (PERF.md section 6); here every reader the
    cell lists that reads the device trace finds its ops in a step shaped
    as this model's (a block of each kind, the scan's loop, the kernels, a
    marked recompute, the expert loop, head, loss and pack)."""
    run = _n3_run()
    cell = Spec(benchmark_tiny.REPO).cell(CELL)
    for name in LISTED + NEW_READERS:
        assert name in cell.per_layer, name
        value = _read(name, run)
        assert value is not None and value > 0, name


def test_the_three_shares_are_least_time_over_their_ops(capsys):
    run = _n3_run()
    for metric, need, ms in (
            ("ssm_scan_roofline",
             parts.scan_train_required(N3_CFG, 1, 8192), 12.0),
            ("relu2_experts_roofline",
             parts.experts_train_required(N3_CFG, 1, 8192)[:2], 6.0),
            # one attention block's seven products over the causal half
            ("flash_nope_roofline",
             (7 * 2.0 * 32 * 8192 * 8192 * 128 / 2,
              flops.flash_train_required(1, 32, 8192, 128, causal=True,
                                         layers=1)[1]), 13.0)):
        least, _ = flops.least_seconds(*need, PEAK)
        got = _read(metric, run)
        assert math.isclose(got, 100 * least / (ms * MS)), metric
        assert 0 < got < 100, metric
        assert f"{metric}: " in capsys.readouterr().out


def test_a_scope_is_matched_whole():
    """``hvd_ssm_in`` never answers for ``hvd_ssm``'s other parts, nor a
    longer name for ``hvd_ssm_scan``."""
    step = [_fusion(1, NF + M0 + "hvd_ssm_scanner/dot_general:", 0, 2),
            _fusion(2, NF + "layers_0/mixer/hvd_ssm_other/mul:", 2, 4)]
    run = _n3_run(step)
    for metric in NEW_READERS:
        if metric in ("ssm_ms",):
            assert math.isclose(_read(metric, run), 2.0)
        else:
            assert _read(metric, run) is None, metric


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_a_nemotron_h_reader_reads_none_where_there_is_nothing_to_read(
        metric, step):
    """The parent of this PR (no such scope, no such configuration key) and
    a cell of another configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    assert _read(metric, run) is None            # GPT-2's keys, no scope
    run.cell.cfg, run.cell.mix = N3_CFG, N3_MIX
    if metric == "flash_nope_roofline" and step == "gpt":
        # the kernels go by their call target, whatever model calls them
        assert _read(metric, run) > 0
    else:
        assert _read(metric, run) is None        # no op under the scope


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_n3_root(tmp_path_factory):
    return benchmark_tiny_nemotron_h.make(
        str(tmp_path_factory.mktemp("bench")))


def test_tiny_nemotron_h_cell_runs_end_to_end(tiny_n3_root, world, capsys):
    """Ids from the generator through ``ShardedLoader``, three state-space
    blocks through the chunked scan, three expert blocks of relu^2 experts
    (held 2..5 of 8) under the load bound and an attention block through
    the flash kernels, through ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_n3_root, "tiny-nemotron-h", 1)
    _well_formed(result, "tiny-nemotron-h", 1)
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


def test_tiny_nemotron_h_cell_in_float8_is_not_correct(tiny_n3_root, world,
                                                       capsys):
    result = _run_cell(tiny_n3_root, "tiny-nemotron-h", 1,
                       break_step=_float8_program)
    assert result["correct"] is False
    assert any("OVER" in line for line in
               capsys.readouterr().out.splitlines()
               if line.startswith("check: "))


def test_tiny_nemotron_h_adds_files_and_entries_and_edits_none(tiny_n3_root,
                                                               tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_n3_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/nemotron_h_tiny.json",
                     "configs/nemotron_h_tiny.py",
                     "traffic/seq64-b2-n3.json"}


# -- the cell in ``BENCHMARK.json`` -----------------------------------------------


def test_what_the_new_cell_reports():
    spec = Spec(benchmark_tiny.REPO)
    mine = spec.cell(CELL)
    assert (mine.config, mine.traffic, mine.chips) == (
        "nemotron3_nano_30b_a3b", "seq8k-b1-n3", 1)
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert {*LISTED, *NEW_READERS} <= set(mine.per_layer)
    assert not set(NOT_LISTED) & set(mine.per_layer)
    bench = _json("BENCHMARK.json")
    # appended: the cell is the last of every list that has it, and the
    # seven new metrics are the last seven, this cell's alone
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "nemotron3_nano_30b_a3b"
    assert len(bench["workloads"][-1]["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert [m["name"] for m in bench["per_layer"][-8:]] == NEW_READERS
    for m in bench["per_layer"][-7:]:
        assert (m["workloads"], m["moves"], m["source"]) == (
            [CELL], "mfu", "device_trace")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"].endswith("_roofline")
            else ("ms", "lower"))
    limits = mine.adapter.limits(mine.cfg, mine.mix)
    assert math.isclose(limits["final_loss"], math.log(16384) + 2.0)
    # the model the adapter builds is the configuration's
    model = mine.adapter.program(mine.cfg, mine.mix)["model"]
    assert "".join(model.kinds()) == PUBLISHED[:mine.cfg[
        "num_hidden_layers"]]
    assert (model.hidden_size, model.vocab_size, model.mamba_num_heads,
            model.mamba_head_dim, model.mamba_groups, model.ssm_state_size,
            model.conv_kernel, model.chunk_size) == (
        2688, 16384, 64, 64, 8, 128, 4, 128)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (
        32, 2, 128)
    assert (model.num_experts, model.router_experts, model.first_expert,
            model.num_experts_per_tok, model.moe_intermediate_size,
            model.shared_intermediate_size, model.routed_scaling_factor) \
        == (8, 128, 0, 6, 1856, 3712, 2.5)
    assert (model.moe_group_rows, model.moe_capacity_factor,
            model.norm_eps) == (4096, 1.25, 1e-5)
    # normal(0, 0.02) for every matrix and Adam at 1e-4, as ISSUE 41 has it:
    # no seeded scale of the configuration's own
    assert mine.cfg["initializer_range"] == 0.02
    assert not [k for k in mine.cfg if k.endswith("_initializer_range")]
    assert mine.cfg["learning_rate"] == 1e-4
    assert model.remat and model.dtype == jnp.bfloat16
    # the sample the eager init runs on is short
    assert mine.adapter.program(mine.cfg, mine.mix)["sample"].shape \
        == (1, 1024)
