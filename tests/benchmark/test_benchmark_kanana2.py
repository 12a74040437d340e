"""ISSUE 34's benchmark tests: the configuration ``kanana2_30b_a3b``, the
traffic ``seq8k-b1-k2``, the cell ``kanana2-8k`` and its seven readers.

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
import benchmark_tiny_kanana2
from benchmarks.configs import kanana2_30b_a3b as adapter
from benchmarks.harness import check, flops, peaks, trace
from benchmarks.harness import kanana2_parts as parts
from benchmarks.harness.spec import Spec
from benchmarks.references import common, kanana2
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

CELL = "kanana2-8k"
KERNEL_SHARES = ["flash_mla_fwd_roofline", "flash_mla_dq_roofline",
                 "flash_mla_dkv_roofline"]
NEW_READERS = ["mla_ms", "mla_latent_ms", "flash_mla_roofline",
               *KERNEL_SHARES, "mla_experts_roofline"]


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


def _cfg():
    return _json("benchmarks", "configs", "kanana2_30b_a3b.json")


# -- parameters and required operations, one chip's share, by hand --------------


def test_kanana2_parameter_count_by_hand():
    cfg = _cfg()
    d = 2048
    w_q, w_kva, w_kvb, w_o = (d * 32 * 192, d * (512 + 64),
                              512 * 32 * (128 + 128), 32 * 128 * d)
    assert (w_q, w_kva, w_kvb, w_o) == (12_582_912, 1_179_648, 4_194_304,
                                        8_388_608)
    attention = w_q + w_kva + 512 + w_kvb + w_o
    assert attention == 26_345_984
    assert parts.attention_matmul_params(cfg) == attention - 512
    dense_mlp, router = 3 * d * 6144, d * 128
    shared, expert = 3 * d * 1536, 3 * d * 768
    assert (dense_mlp, router, shared, expert) == (
        37_748_736, 262_144, 9_437_184, 4_718_592)
    assert parts.expert_params(cfg) == expert
    expert_layer = attention + router + shared + 8 * expert + 2 * d
    dense_layer = attention + dense_mlp + 2 * d
    assert (expert_layer, dense_layer) == (73_798_144, 64_098_816)
    table = 16032 * d
    assert table == 32_833_536
    assert parts.parameters(cfg) == dense_layer + 4 * expert_layer \
        + 2 * table + d == 424_960_512
    # 16 B a parameter for training, 20 B while run.py holds the benchmark's
    # weights through the checked steps, 36 B in the reference's update
    n = parts.parameters(cfg)
    assert math.isclose(16 * n, 6.80e9, rel_tol=1e-3)
    assert math.isclose(20 * n, 8.50e9, rel_tol=1e-3)
    assert math.isclose(36 * n, 15.30e9, rel_tol=1e-3)
    # 16 held experts: 576 M, and the reference's update 20.7 GB
    wider = parts.parameters(dict(cfg, n_routed_experts=16))
    assert math.isclose(wider, 576e6, rel_tol=1e-3)
    assert math.isclose(36 * wider, 20.7e9, rel_tol=2e-3)
    # the uncut model by the same count: 30.67 B
    whole = dict(cfg, num_hidden_layers=48, n_routed_experts=128,
                 vocab_size=128256)
    assert math.isclose(parts.parameters(whole), 30.67e9, rel_tol=1e-3)


def test_kanana2_train_flops_per_token_by_hand():
    cfg = _cfg()
    projections = 2 * 26_345_472
    assert math.isclose(projections, 52.69e6, rel_tol=1e-4)
    # 4096.5 causal pairs a row, a head a pair: q.k over 192, P v over 128
    assert parts.causal_pairs(8192) == 8192 * 8193 // 2
    scores = 4096.5 * 32 * (192 + 128) * 2
    assert math.isclose(scores, 83.9e6, rel_tol=1e-3)
    dense = 2 * 37_748_736
    expert_layer = 2 * (262_144 + 9_437_184 + 6 * 8 / 128 * 4_718_592)
    assert math.isclose(expert_layer, 0.52e6 + 18.87e6 + 3.54e6,
                        rel_tol=1e-3)
    head = 2 * 2048 * 16032
    forward = 5 * (projections + scores) + dense + 4 * expert_layer + head
    assert math.isclose(parts.forward_flops_per_token(cfg, 8192), forward)
    assert math.isclose(forward, 915.9e6, rel_tol=1e-4)
    assert math.isclose(parts.train_flops_per_token(cfg, 8192), 2747.6e6,
                        rel_tol=1e-4)
    assert math.isclose(adapter.flops_per_item(cfg, _json(
        "benchmarks", "traffic", "seq8k-b1-k2.json")), 3 * forward)
    # latent attention through the flash kernels is 46% of it
    assert 0.455 < 5 * scores / forward < 0.465
    # a step: 22.5 TFLOP, least 114 ms at the v5e's peak
    step = 8192 * 3 * forward
    assert math.isclose(step, 22.5e12, rel_tol=1e-3)
    assert math.isclose(step / peaks.PEAKS["TPU v5 lite"].flops, 0.1143,
                        rel_tol=2e-3)


def test_kanana2_flash_and_expert_requirements_by_hand():
    cfg = _cfg()
    pairs = 8192 * 8193 // 2
    unit = 2.0 * 32 * pairs
    ops, nbytes = parts.flash_train_required(cfg, 1, 8192)
    # QK^T, the scores again, dK, dQ at 192; PV, dP, dV at 128
    assert ops == 5 * unit * (4 * 192 + 3 * 128)
    column, rows = 32 * 8192 * 2, 32 * 8192 * 4
    assert nbytes == 5 * (column * (2 * 192 + 2 * 128) + rows
                          + column * (4 * 192 + 4 * 128) + 2 * rows)
    assert flops.least_seconds(ops, nbytes, PEAK)[1] == "compute"
    # each kernel's own products: 2 / 3 / 4, nine where the whole has seven
    want = {"fwd": (192 + 128, 2 * 192 + 2 * 128, 1),
            "dq": (2 * 192 + 128, 3 * 192 + 2 * 128, 2),
            "dkv": (2 * 192 + 2 * 128, 3 * 192 + 3 * 128, 2)}
    for kernel, (width, columns, stats) in want.items():
        k_ops, k_bytes = parts.flash_kernel_required(cfg, kernel, 1, 8192)
        assert k_ops == 5 * unit * width, kernel
        assert k_bytes == 5 * (column * columns + stats * rows), kernel
    assert sum(parts.flash_kernel_required(cfg, k, 1, 8192)[0]
               for k in want) == ops + 5 * unit * (192 + 128)
    # at equal head sizes the count is the accepted one's (s^2 / 2 pairs
    # there, s (s + 1) / 2 here)
    equal = dict(cfg, qk_nope_head_dim=64, qk_rope_head_dim=64)
    accepted = flops.flash_train_required(1, 32, 8192, 128, causal=True,
                                          layers=5)
    mine = parts.flash_train_required(equal, 1, 8192)
    assert math.isclose(mine[0] / accepted[0], 8193 / 8192)
    assert mine[1] == accepted[1]
    # the experts: 3072 expected assignments a layer, four expert layers
    ops, nbytes, assignments = parts.experts_train_required(cfg, 1, 8192)
    assert assignments == 8192 * 6 * 8 / 128 == 3072
    assert ops == 4 * 3 * 2 * 3072 * 3 * 2048 * 768
    weights = 8 * 3 * 2048 * 768
    rows_bytes = 3072 * (2 * 2048 + 3 * 768) * 2
    assert nbytes == 4 * (2 * (weights * 2 + rows_bytes)
                          + weights * 4 + rows_bytes)
    # 384 rows an expert: the weights' traffic binds, not the products
    assert flops.least_seconds(ops, nbytes, PEAK)[1] == "memory"


# -- the files' form ----------------------------------------------------------


def test_kanana2_files_state_the_cut_and_the_traffic_of_its_cell():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "kanana2_30b_a3b")
    cfg = _json(entry["file"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["source"] == entry["source"] \
        == "https://huggingface.co/kakaocorp/" \
           "kanana-2-30b-a3b-instruct-2601/blob/main/config.json"
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 16032)
    assert cfg["router_num_experts"] == 128 and cfg["first_expert"] == 0
    assert "16 chips" in cfg["deployment"]
    assert {"training_recipe", "loss", "selection_bias", "weights",
            "expert_capacity"} <= set(cfg["assumed"])
    assert "36 B" in cfg["reduced"]["n_routed_experts"]
    # every number of the source's config under its key, but the three cut
    source = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in source} == source
    # no width is cut: the keys the contract forbids in `reduced`
    for key in ("hidden_size", "qk_head_dim", "v_head_dim", "kv_lora_rank",
                "moe_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "n_shared_experts"):
        assert key not in cfg["reduced"]
    # the load is bounded as GShard bounds it, an expert's capacity of a
    # group under one tile of the expert layer; the q projections' seeded
    # deviation is set by rule (why: assumed.weights)
    assert (cfg["moe_group_rows"], cfg["moe_capacity_factor"]) == (4096, 1.25)
    assert math.ceil(1.25 * 4096 * 6 / 128) == 240
    assert "2006.16668" in cfg["assumed"]["expert_capacity"]
    assert cfg["q_proj_initializer_range"] == 0.146 \
        and "q_proj_initializer_range" in cfg["assumed"]["weights"]
    deviation = 0.146 * math.sqrt(2048) * 192 ** -0.5 * math.sqrt(
        128 * 0.02 ** 2 * 512 + 64 * 0.02 ** 2 * 2048)
    assert abs(deviation - math.sqrt(2 * math.log(8192))) < 0.05
    # the traffic is seq8k-b1's numbers in a file of its own
    assert _json("benchmarks", "traffic", "seq8k-b1-k2.json") \
        == _json("benchmarks", "traffic", "seq8k-b1.json") == {
        "rows_per_chip": 1, "dataset_rows_per_chip": 64,
        "arrays": [{"name": "ids", "shape": [8192], "dtype": "int32",
                    "low": 0, "high": "vocab_size"}],
        "items_per_row": 8192, "rate_metric": "tokens_per_s_chip"}


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(benchmark_tiny.REPO, "benchmarks", "references",
                        "kanana2.py")
    with open(path) as fh:
        code = [line for line in fh if line.startswith(("import ", "from "))]
    assert code and not any("horovod_tpu" in line for line in code)


# -- the control --------------------------------------------------------------

TOY = benchmark_tiny_kanana2.KANANA2_TINY
#: The cell's limits are read on the chip at the cell's size.  The toy is
#: float32, three layers and 64 tokens: a sound program reads 1e-6 and its
#: float8 control 0.08 and more, so the toy holds the control to a limit
#: between those.
TOY_LIMITS = dict(adapter.LIMITS, grad_sketch_gap=0.02)


def _toy_batch(seed, rows=2, length=64):
    return (np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], (rows, length)).astype(np.int32),)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_kanana2(seed):
    ref = {"init": lambda s: kanana2.seeded_weights(TOY, seed),
           "loss": lambda p: kanana2.loss_fn(TOY, p), "optimizer": "adam",
           "lr": 1e-4}
    batches = [_toy_batch(seed * 10 + i) for i in range(3)]
    numbers = check.first_steps_numbers(
        common.follow(ref, 0, batches, 2, "fp8"),
        common.follow(ref, 0, batches, 2))
    correct, lines = check.verdict(
        numbers, {k: TOY_LIMITS[k] for k in numbers})
    assert not correct, lines
    assert numbers["grad_sketch_gap"] > 2 * TOY_LIMITS["grad_sketch_gap"]


# -- the seven readers on a hand-built trace ------------------------------------

K2_CFG = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 6144, "num_attention_heads": 32,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "kv_lora_rank": 512, "moe_intermediate_size": 768,
    "n_routed_experts": 8, "router_num_experts": 128,
    "num_experts_per_tok": 6, "n_shared_experts": 2, "vocab_size": 16032}
K2_MIX = {"rows_per_chip": 1, "arrays": [{"shape": [8192]}]}
KF = "jit(s)/jvp(hvd_forward)/Kanana2/"
KB = "jit(s)/transpose(jvp(hvd_forward))/Kanana2/"
A = "layers_1/self_attn/hvd_mla/"
#: one step: (HLO text, tf_op, start ms, end ms)
K2_STEP = [
    ("%fusion.1 = bf16[8] fusion(%p)", KF + A + "hvd_mla_q/dot_general:",
     0, 1),
    ("%fusion.2 = bf16[8] fusion(%p)",
     KF + A + "hvd_mla_latent/dot_general:", 1, 2.5),
    ("%fusion.3 = bf16[8] fusion(%p)",
     KF + A + "hvd_mla_latent/concatenate:", 2.5, 3),
    ("%hvd_flash_fwd.4 = bf16[8]" + MOSAIC,
     KF + A + "hvd_flash_fwd/pallas_call:", 3, 7),
    ("%fusion.5 = bf16[8] fusion(%p)", KF + A + "o_proj/dot_general:", 7, 8),
    ("%fusion.6 = bf16[8] fusion(%p)",
     KF + "layers_0/mlp/hvd_dense_mlp/dot_general:", 8, 9),
    ("%fusion.7 = f32[8] fusion(%p)",
     KF + "layers_1/mlp/hvd_moe/hvd_moe_route/top_k:", 9, 10),
    ("%while.8 = (s32[]) while(%t)", KF + "layers_1/mlp/hvd_moe/while:",
     10, 12),
    ("%fusion.9 = f32[8] fusion(%p)",
     KF + "layers_1/mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     10, 11.5),
    ("%fusion.10 = f32[8] fusion(%p)",
     KF + "layers_1/mlp/hvd_moe/while/body/hvd_moe_route/scatter-add:",
     11.5, 12),
    ("%fusion.11 = bf16[8] fusion(%p)",
     KF + "layers_1/mlp/hvd_moe/hvd_moe_shared/dot_general:", 12, 13),
    ("%hvd_flash_dq.12 = bf16[8]" + MOSAIC,
     KB + A + "hvd_flash_dq/pallas_call:", 14, 19),
    ("%hvd_flash_dkv.13 = bf16[8]" + MOSAIC,
     KB + A + "hvd_flash_dkv/pallas_call:", 19, 25),
    ("%fusion.14 = bf16[8] fusion(%p)",
     KB + A + "hvd_mla_latent/dot_general:", 25, 27),
    ("%fusion.15 = f32[8] fusion(%p)",
     KB + "layers_1/mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     27, 29.5),
    ("%fusion.16 = f32[10] fusion(%p)",
     "jit(s)/hvd_optimizer_update/add:", 29.5, 30),
]


def _k2_run(step=K2_STEP, cfg=K2_CFG) -> RunRecord:
    ops = [trace.Op(name, (30 * i + a) * MS, (30 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": cfg, "mix": K2_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=30 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 30 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


def test_mla_ms_and_mla_latent_ms_read_their_scopes():
    run = _k2_run()
    # q 1, latent 2, forward kernel 4, o_proj 1; dq 5, dkv 6, latent 2
    assert math.isclose(_read("mla_ms", run), 8.0 + 13.0)
    assert math.isclose(_read("mla_latent_ms", run), 2.0 + 2.0)
    # the accepted readers find the same kernels and scopes by name
    assert math.isclose(_read("flash_ms", run), 15.0)
    assert math.isclose(_read("flash_fwd_ms", run), 4.0)
    assert math.isclose(_read("moe_route_ms", run), 1.5)
    # the loop's envelope and its body are one interval; shared counts
    assert math.isclose(_read("moe_ms", run), 4.0 + 2.5)
    assert math.isclose(_read("moe_tiles", run), 1.0)


def test_flash_mla_roofline_is_least_time_over_the_three_kernels(capsys):
    need = parts.flash_train_required(K2_CFG, 1, 8192)
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read("flash_mla_roofline", _k2_run())
    assert math.isclose(got, 100.0 * least / (15.0 * MS))
    assert "flash_mla_roofline:" in capsys.readouterr().out


@pytest.mark.parametrize("kernel,ms,width", [
    ("fwd", 4.0, 192 + 128), ("dq", 5.0, 2 * 192 + 128),
    ("dkv", 6.0, 2 * 192 + 2 * 128)])
def test_each_kernels_share_at_latent_attentions_head_sizes(
        capsys, kernel, ms, width):
    need = parts.flash_kernel_required(K2_CFG, kernel, 1, 8192)
    assert need[0] == 5 * 2.0 * 32 * (8192 * 8193 // 2) * width
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read(f"flash_mla_{kernel}_roofline", _k2_run())
    assert math.isclose(got, 100.0 * least / (ms * MS))
    assert f"flash_mla_{kernel}_roofline:" in capsys.readouterr().out


def test_mla_experts_roofline_counts_the_held_experts_of_its_own_key(capsys):
    ops, nbytes, rows = parts.experts_train_required(K2_CFG, 1, 8192)
    least, _ = flops.least_seconds(ops, nbytes, PEAK)
    got = _read("mla_experts_roofline", _k2_run())
    assert math.isclose(got, 100.0 * least / (4.0 * MS))
    assert "3072 expected assignments a layer" in capsys.readouterr().out
    # the accepted reader looks for ``num_experts``: why the cell lists this
    # reader and not ``moe_experts_roofline``
    with pytest.raises(KeyError):
        _read("moe_experts_roofline", _k2_run())


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_a_kanana2_reader_reads_none_where_there_is_nothing_to_read(
        metric, step):
    """The parent of this PR (no such scope, no such configuration key) and
    a cell of another configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    assert _read(metric, run) is None            # GPT-2's keys: no latent kv
    run.cell.cfg, run.cell.mix = K2_CFG, K2_MIX
    if step == "conv" or not metric.startswith("flash_mla_"):
        assert _read(metric, run) is None        # no op under the scope


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_k2_root(tmp_path_factory):
    return benchmark_tiny_kanana2.make(
        str(tmp_path_factory.mktemp("bench")))


def test_tiny_kanana2_cell_runs_end_to_end(tiny_k2_root, world, capsys):
    """Ids from the generator through ``ShardedLoader``, latent attention
    through the flash kernels at q.k 24 / v 16, the dense layer, the routed
    experts (held 2..5 of 8) under the sigmoid rule with the shared experts,
    through ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_k2_root, "tiny-kanana2", 1)
    _well_formed(result, "tiny-kanana2", 1)
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


def test_tiny_kanana2_cell_in_float8_is_not_correct(tiny_k2_root, world,
                                                     capsys):
    result = _run_cell(tiny_k2_root, "tiny-kanana2", 1,
                       break_step=_float8_program)
    assert result["correct"] is False
    assert any("OVER" in line for line in
               capsys.readouterr().out.splitlines()
               if line.startswith("check: "))


def test_tiny_kanana2_adds_files_and_entries_and_edits_none(tiny_k2_root,
                                                            tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_k2_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/kanana2_tiny.json", "configs/kanana2_tiny.py",
                     "traffic/seq64-b2-k2.json"}


# -- the cell in ``BENCHMARK.json`` -----------------------------------------------
# (which accepted readers list it is ``test_benchmark_lists.py``'s)


def test_the_cell_reports_its_readers_and_builds_the_configurations_model():
    spec = Spec(benchmark_tiny.REPO)
    mine = spec.cell(CELL)
    assert (mine.config, mine.traffic, mine.chips) == (
        "kanana2_30b_a3b", "seq8k-b1-k2", 1)
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert set(NEW_READERS) <= set(mine.per_layer)
    limits = mine.adapter.limits(mine.cfg, mine.mix)
    assert math.isclose(limits["final_loss"], math.log(16032) + 2.0)
    # the model the adapter builds is the configuration's
    model = mine.adapter.program(mine.cfg, mine.mix)["model"]
    assert (model.num_layers, model.num_experts, model.router_experts,
            model.num_experts_per_tok, model.num_shared_experts,
            model.routed_scaling_factor) == (5, 8, 128, 6, 2, 2.448)
    assert (model.qk_nope_head_dim + model.qk_rope_head_dim,
            model.v_head_dim, model.kv_lora_rank) == (192, 128, 512)
    assert model.selection_bias is None and model.dtype == jnp.bfloat16
