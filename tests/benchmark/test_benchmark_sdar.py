"""ISSUE 30's benchmark tests: the configuration ``sdar_30b_a3b_chat``, the
traffic ``seq8k-b1-bd4``, the cell ``sdar-bd4-8k`` with its three readers,
and the second cell ``gpt2s-4k`` (traffic ``seq4k-b2``).

A file of its own because the other files of this directory are the
benchmark's (``BENCHMARK.json`` lists ``tests/benchmark`` under ``paths``)
and a PR that changes the program may only add beside them.  Which cells
list which metric, every cell's files and the toy benchmarks' form follow
``BENCHMARK.json`` in ``test_benchmark_lists.py``, ``test_benchmark_harness.py``
and ``test_benchmark_form.py`` (PR 40)."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
import benchmark_tiny_sdar
from benchmarks.configs import sdar_30b_a3b_chat as adapter
from benchmarks.harness import check, flops, peaks, trace
from benchmarks.harness import sdar_parts as parts
from benchmarks.harness.spec import Spec
from benchmarks.references import common, sdar
from benchmarks.run import RunRecord
from test_benchmark_harness import _run as _run_cell, _well_formed
from test_benchmark_harness import world  # noqa: F401 — a fixture
from test_benchmark_parts import (CONV_STEP, GPT_STEP, MOSAIC, MS, PEAK,
                                   STEPS, _read, _run)

KERNEL_SHARES = ["flash_bd_fwd_roofline", "flash_bd_dq_roofline",
                 "flash_bd_dkv_roofline"]
NEW_READERS = ["flash_bd_roofline", "bd_experts_roofline", "bd_noise_ms",
               *KERNEL_SHARES]


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


def _sdar_cfg():
    return _json("benchmarks", "configs", "sdar_30b_a3b_chat.json")


# -- parameters and required operations, one chip's share, by hand --------------


def test_sdar_parameter_count_by_hand():
    cfg = _sdar_cfg()
    d = 2048
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    assert attention == 18_874_368
    expert = 3 * d * 768
    assert expert == 4_718_592
    layer = attention + 2 * 128 + d * 128 + 2 * d + 16 * expert
    assert layer == 94_638_336
    assert parts.parameters(cfg) == 4 * layer + 2 * 18992 * d + d \
        == 456_346_624
    # 16 B a parameter for training, 20 B while run.py holds the benchmark's
    # weights through the checked steps
    assert math.isclose(16 * parts.parameters(cfg), 7.30e9, rel_tol=1e-3)
    assert math.isclose(20 * parts.parameters(cfg), 9.13e9, rel_tol=1e-3)
    # the uncut model by the same count: 30.5 B; six layers would be 645.6 M
    whole = dict(cfg, num_hidden_layers=48, num_experts=128,
                 vocab_size=151936)
    assert math.isclose(parts.parameters(whole), 30.5e9, rel_tol=2e-3)
    assert math.isclose(parts.parameters(dict(cfg, num_hidden_layers=6)),
                        645.6e6, rel_tol=1e-4)


def test_sdar_train_flops_per_data_token_by_hand():
    """Two decoder rows a data token through the layers' products (an
    expert's at the 8 x 16 / 128 assignments an even router sends), the
    scores and values of its 8192 + 4 allowed pairs, the head on the
    noised row alone; times three for training."""
    cfg = _sdar_cfg()
    assert parts.allowed_pairs(8192, 4) == 8192 ** 2 + 4 * 8192
    row = 18_874_368 + 2048 * 128 + 1.0 * 4_718_592
    assert math.isclose(parts.layer_matmul_params_per_row(cfg), row)
    attention = 4 * 128 * 32 * 8196
    head = 2 * 2048 * 18992
    forward = 4 * (2 * 2 * row + attention) + head
    assert math.isclose(parts.forward_flops_per_token(cfg, 8192), forward)
    assert math.isclose(forward, 996.6e6, rel_tol=1e-4)
    assert math.isclose(parts.train_flops_per_token(cfg, 8192), 2989.8e6,
                        rel_tol=1e-4)
    assert math.isclose(adapter.flops_per_item(cfg, _json(
        "benchmarks", "traffic", "seq8k-b1-bd4.json")), 3 * forward)
    # attention under the new mask is over half of it
    assert 0.53 < 4 * attention / forward < 0.55
    # a step: 24.5 TFLOP, least 124 ms at the v5e's peak
    step = 8192 * 3 * forward
    assert math.isclose(step, 24.49e12, rel_tol=1e-3)
    assert math.isclose(step / peaks.PEAKS["TPU v5 lite"].flops, 0.1243,
                        rel_tol=1e-3)


def test_sdar_flash_and_expert_requirements_by_hand():
    cfg = _sdar_cfg()
    ops, nbytes = parts.flash_train_required(cfg, 1, 8192)
    pairs = 8192 * 8192 + 4 * 8192
    assert ops == 4 * 7 * 2 * 32 * 128 * pairs
    tensor, rows = 32 * 16384 * 128 * 2, 32 * 16384 * 4
    assert nbytes == 4 * ((4 * tensor + rows) + (8 * tensor + 2 * rows))
    # a causal mask over the same 2 x 8192 rows would need twice the work
    causal = flops.flash_train_required(1, 32, 16384, 128, causal=True,
                                        layers=4)
    assert math.isclose(causal[0] / ops, 2.0, rel_tol=1e-3)
    assert causal[1] == nbytes
    assert flops.least_seconds(ops, nbytes, PEAK)[1] == "compute"
    ops, nbytes, assignments = parts.experts_train_required(cfg, 1, 8192)
    assert assignments == 2 * 8192 * 8 * 16 / 128 == 16384
    assert ops == 4 * 3 * 2 * 16384 * 3 * 2048 * 768
    weights = 16 * 3 * 2048 * 768
    rows_bytes = 16384 * (2 * 2048 + 3 * 768) * 2
    assert nbytes == 4 * (2 * (weights * 2 + rows_bytes)
                          + weights * 4 + rows_bytes)
    # 1024 rows an expert: the products bind, not the weights' traffic
    assert flops.least_seconds(ops, nbytes, PEAK)[1] == "compute"


# -- the files' form ----------------------------------------------------------


def test_sdar_files_state_the_cut_and_the_traffic_of_its_cell():
    spec = _json("BENCHMARK.json")
    entry = next(c for c in spec["configs"]
                 if c["name"] == "sdar_30b_a3b_chat")
    cfg = _json(entry["file"])
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["source"] == entry["source"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["router_num_experts"] == 128 and cfg["first_expert"] == 0
    assert cfg["mask_token_id"] == cfg["data_vocab_size"] \
        == cfg["vocab_size"] - 1
    assert "8 chips" in cfg["deployment"]
    assert {"block_length", "noise_schedule", "shift", "mask_token",
            "loss", "weights"} <= set(cfg["assumed"])
    # the heads' q and k norms start at 2 (why: assumed.weights); the load
    # is bounded as GShard bounds it, an expert's capacity of a group one
    # tile of the expert layer (why: assumed.expert_capacity)
    assert cfg["qk_norm_init"] == 2.0 and "qk_norm_init" in \
        cfg["assumed"]["weights"]
    assert (cfg["moe_group_rows"], cfg["moe_capacity_factor"]) == (4096, 1.0)
    assert cfg["moe_capacity_factor"] * cfg["moe_group_rows"] \
        * cfg["num_experts_per_tok"] / cfg["router_num_experts"] == 256
    assert "2006.16668" in cfg["assumed"]["expert_capacity"]
    assert not any(k.startswith("moe_block") for k in cfg)
    # every number of the source's config under its key, but the three cut
    source = {
        "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000}
    assert {k: cfg[k] for k in source} == source
    assert cfg["model_type"] == "sdar_moe" and cfg["norm_topk_prob"] is True
    # no width is cut: the keys the contract forbids in `reduced`
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "intermediate_size"):
        assert key not in cfg["reduced"]
    assert _json("benchmarks", "traffic", "seq8k-b1-bd4.json") == {
        "rows_per_chip": 1, "dataset_rows_per_chip": 64,
        "arrays": [
            {"name": "ids", "shape": [8192], "dtype": "int32", "low": 0,
             "high": "data_vocab_size"},
            {"name": "level", "shape": [2048], "dtype": "int32",
             "low": 4096, "high": 65537},
            {"name": "draw", "shape": [8192], "dtype": "int32", "low": 0,
             "high": 65536}],
        "items_per_row": 8192, "rate_metric": "tokens_per_s_chip"}
    assert _json("benchmarks", "traffic", "seq4k-b2.json") == {
        "rows_per_chip": 2, "dataset_rows_per_chip": 128,
        "arrays": [{"name": "ids", "shape": [4096], "dtype": "int32",
                    "low": 0, "high": "vocab_size"}],
        "items_per_row": 4096, "rate_metric": "tokens_per_s_chip"}


def test_the_traffic_never_draws_the_mask_and_clips_the_schedule():
    from benchmarks.harness import traffic

    cfg = _sdar_cfg()
    mix = dict(_json("benchmarks", "traffic", "seq8k-b1-bd4.json"),
               dataset_rows_per_chip=4)
    ids, level, draw = traffic.dataset(mix, cfg, 1, 2 ** 31 + 5)
    assert ids.shape == (4, 8192) and level.shape == (4, 2048)
    assert ids.max() < cfg["mask_token_id"]
    assert level.min() >= 4096 and level.max() <= 65536   # t in [1/16, 1]
    assert 0 <= draw.min() and draw.max() < 65536
    # a weight 1 / t is at most 16, and the masked share is the mean of t
    masked = draw < np.repeat(level, 4, axis=1)
    assert abs(masked.mean() - (level / 65536).mean()) < 0.01


# -- the reference against itself ------------------------------------------------

TOY = benchmark_tiny_sdar.SDAR_TINY


def _toy_batch(seed, rows=2, length=64):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 255, (rows, length)), jnp.int32),
            jnp.asarray(rng.integers(4096, 65537, (rows, length // 4)),
                        jnp.int32),
            jnp.asarray(rng.integers(0, 65536, (rows, length)), jnp.int32))


def test_reference_in_blocks_is_the_reference_unblocked(monkeypatch):
    """Attention in query blocks and the head in row blocks, as the chip's
    size needs them, against both whole: loss and every gradient leaf."""
    params = common.unflatten(sdar.seeded_weights(TOY, 11))
    batch = _toy_batch(3)
    whole = jax.value_and_grad(sdar.loss_fn(TOY))(params, *batch)
    monkeypatch.setattr(sdar, "QUERY_BLOCK", 16)
    monkeypatch.setattr(sdar, "TOKEN_BLOCK", 48)   # 128 rows pad to 144
    seen = sdar.allowed_pairs(64, 4)
    q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 128, 2, 8)),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(sdar.masked_attention(q, q, q, seen, lambda a: a, 16)),
        np.asarray(sdar.masked_attention(q, q, q, seen, lambda a: a, 128)),
        atol=1e-6)
    blocked = jax.value_and_grad(
        lambda p, *b: sdar.weighted_head_loss(
            sdar.hidden_fn(TOY, lambda a: a)(p, *b).reshape(128, -1),
            p["lm_head"], b[0].reshape(128), _weights(b), lambda a: a, 48))(
                params, *batch)
    assert abs(float(blocked[0]) - float(whole[0])) < 1e-6 * float(whole[0])
    for name, w in common.flatten(whole[1]).items():
        got = common.flatten(blocked[1])[name]
        assert float(jnp.linalg.norm(got - w)) <= 1e-4 * float(
            jnp.linalg.norm(w)), name


def _weights(batch):
    ids, level, draw = batch
    t = jnp.repeat(level.astype(jnp.float32) / 65536, 4, axis=1)
    return (sdar.noised(ids, level, draw, 4) / t / ids.size).reshape(-1)


def test_reference_mask_is_the_definition_pair_by_pair():
    seen = np.asarray(sdar.allowed_pairs(12, 4))
    for i in range(24):
        for j in range(24):
            ci, cj = i // 12, j // 12
            gi, gj = (i % 12) // 4, (j % 12) // 4
            assert seen[i, j] == ((cj == 1 and gj < gi + ci) or (
                ci == 0 and cj == 0 and gj == gi)), (i, j)
    assert seen.sum() == parts.allowed_pairs(12, 4)


#: The cell's limits are read on the chip at the cell's size, where the
#: float8 control's ``grad_sketch_gap`` is 0.96 to 1.04 against a sound 0.25
#: to 0.28 (q and k norms seeded at 2 make bfloat16's scores show).  The toy
#: is float32, two layers and 64 tokens: its control reads 0.26 to 0.30 and a
#: sound program 1e-6, so the toy holds the control to a limit between those.
TOY_LIMITS = dict(adapter.LIMITS, grad_sketch_gap=0.1)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_sdar(seed):
    ref = {"init": lambda s: sdar.seeded_weights(TOY, seed),
           "loss": lambda p: sdar.loss_fn(TOY, p), "optimizer": "adam",
           "lr": 1e-4}
    batches = [tuple(np.asarray(a) for a in _toy_batch(seed * 10 + i))
               for i in range(3)]
    numbers = check.first_steps_numbers(
        common.follow(ref, 0, batches, 2, "fp8"),
        common.follow(ref, 0, batches, 2))
    correct, lines = check.verdict(
        numbers, {k: TOY_LIMITS[k] for k in numbers})
    assert not correct, lines
    assert numbers["grad_sketch_gap"] > 2 * TOY_LIMITS["grad_sketch_gap"]


# -- the three readers on a hand-built trace ----------------------------------------

SDAR_CFG = {
    "num_hidden_layers": 4, "hidden_size": 2048, "head_dim": 128,
    "num_attention_heads": 32, "num_key_value_heads": 4,
    "moe_intermediate_size": 768, "num_experts": 16,
    "router_num_experts": 128, "num_experts_per_tok": 8,
    "vocab_size": 18992, "block_length": 4}
SDAR_MIX = {"rows_per_chip": 1,
            "arrays": [{"shape": [8192]}, {"shape": [2048]},
                       {"shape": [8192]}]}
SF = "jit(s)/jvp(hvd_forward)/SDAR/"
SB = "jit(s)/transpose(jvp(hvd_forward))/SDAR/"
#: one step: (HLO text, tf_op, start ms, end ms)
SDAR_STEP = [
    ("%fusion.1 = s32[8] fusion(%p)", SF + "hvd_bd_noise/select_n:", 0, 0.5),
    ("%fusion.2 = f32[8] fusion(%p)", SF + "hvd_bd_noise/cos:", 0.5, 1),
    ("%hvd_flash_fwd.3 = bf16[8]" + MOSAIC,
     SF + "layers_0/self_attn/hvd_flash_fwd/pallas_call:", 1, 5),
    ("%fusion.4 = f32[8] fusion(%p)",
     SF + "layers_0/mlp/hvd_moe/hvd_moe_route/top_k:", 5, 7),
    ("%while.5 = (s32[]) while(%t)", SF + "layers_0/mlp/hvd_moe/while:",
     7, 10),
    ("%fusion.6 = f32[8] fusion(%p)",
     SF + "layers_0/mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     7, 9),
    ("%fusion.7 = f32[8] fusion(%p)",
     SF + "layers_0/mlp/hvd_moe/while/body/hvd_moe_route/scatter-add:",
     9, 10),
    ("%fusion.8 = bf16[8] fusion(%p)", SF + "hvd_bd_head_rows/slice:",
     10, 10.25),
    ("%hvd_flash_fwd.9 = bf16[8]" + MOSAIC,
     SB + "layers_0/self_attn/hvd_flash_fwd/pallas_call:", 11, 15),
    ("%hvd_flash_dq.10 = bf16[8]" + MOSAIC,
     SB + "layers_0/self_attn/hvd_flash_dq/pallas_call:", 15, 18),
    ("%hvd_flash_dkv.11 = bf16[8]" + MOSAIC,
     SB + "layers_0/self_attn/hvd_flash_dkv/pallas_call:", 18, 22),
    ("%fusion.12 = f32[8] fusion(%p)",
     SB + "layers_0/mlp/hvd_moe/while/body/hvd_moe_experts/dot_general:",
     22, 25),
    ("%fusion.13 = f32[10] fusion(%p)",
     "jit(s)/hvd_optimizer_update/add:", 25, 26),
]


def _sdar_run(step=SDAR_STEP, cfg=SDAR_CFG) -> RunRecord:
    ops = [trace.Op(name, (26 * i + a) * MS, (26 * i + b) * MS, tf_op)
           for i in range(STEPS) for name, tf_op, a, b in step]
    cell = type("Cell", (), {"cfg": cfg, "mix": SDAR_MIX})
    return RunRecord(cell, 1, "TPU v5 lite", PEAK, steps=STEPS,
                     window_s=26 * STEPS * MS, reduced=trace.Reduced(
                         (0.0, 26 * STEPS * MS),
                         [trace.ChipTrace(ops, [])], {}))


def test_flash_bd_roofline_is_least_time_over_the_three_kernels(capsys):
    need = parts.flash_train_required(SDAR_CFG, 1, 8192)
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read("flash_bd_roofline", _sdar_run())
    # the hand-built trace's kernels: forward 4 + 4, dq 3, dkv 4 ms a step
    assert math.isclose(got, 100.0 * least / (15.0 * MS))
    assert "flash_bd_roofline:" in capsys.readouterr().out
    # the accepted readers find the same kernels by name
    assert math.isclose(_read("flash_ms", _sdar_run()), 15.0)
    assert math.isclose(_read("flash_fwd_ms", _sdar_run()), 8.0)


@pytest.mark.parametrize("kernel,ms,products", [
    ("fwd", 8.0, 2), ("dq", 3.0, 3), ("dkv", 4.0, 4)])
def test_each_kernels_share_under_the_block_diffusion_mask(
        capsys, kernel, ms, products):
    """``flash_<kernel>_roofline``'s sibling: the kernel's own products (2
    / 3 / 4 of the nine the three compute) over the allowed pairs, against
    that kernel's time alone (the forward's two calls a layer both
    count)."""
    need = parts.flash_kernel_required(SDAR_CFG, kernel, 1, 8192)
    pairs = 8192 * 8192 + 8192 * 4
    assert need[0] == 4 * products * 2.0 * 32 * 128 * pairs
    least, bound = flops.least_seconds(*need, PEAK)
    assert bound == "compute"
    got = _read(f"flash_bd_{kernel}_roofline", _sdar_run())
    assert math.isclose(got, 100.0 * least / (ms * MS))
    assert f"flash_bd_{kernel}_roofline:" in capsys.readouterr().out
    # the seven products of the whole are the nine less the two computed
    # twice
    whole = parts.flash_train_required(SDAR_CFG, 1, 8192)[0]
    assert math.isclose(need[0] / products, whole / 7)


def test_bd_experts_roofline_counts_both_copies_rows(capsys):
    ops, nbytes, rows = parts.experts_train_required(SDAR_CFG, 1, 8192)
    least, _ = flops.least_seconds(ops, nbytes, PEAK)
    got = _read("bd_experts_roofline", _sdar_run())
    assert math.isclose(got, 100.0 * least / (5.0 * MS))
    assert "16384 expected assignments a layer" in capsys.readouterr().out
    # the accepted reader takes the rows from the traffic's first array
    # (8192) and would charge half the assignments: why the cell lists
    # this reader and not ``moe_experts_roofline``
    assert parts.moe_parts.experts_train_required(SDAR_CFG, 1, 8192)[2] \
        == rows / 2


def test_bd_noise_ms_reads_its_scope_and_the_accepted_readers_theirs():
    run = _sdar_run()
    assert math.isclose(_read("bd_noise_ms", run), 1.0)
    assert math.isclose(_read("moe_route_ms", run), 3.0)
    # the loop's envelope and its body are one interval
    assert math.isclose(_read("moe_ms", run), 5.0 + 3.0)
    assert math.isclose(_read("moe_tiles", run), 1.0)


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("step", ["gpt", "conv"])
def test_an_sdar_reader_reads_none_where_there_is_nothing_to_read(
        metric, step):
    """The parent of this PR (no such scope, no such configuration key) and
    a cell of another configuration: nothing to read, no error."""
    run = _run({"gpt": GPT_STEP, "conv": CONV_STEP}[step])
    if metric != "bd_noise_ms":
        assert _read(metric, run) is None        # GPT-2's keys: no block
    run.cell.cfg, run.cell.mix = SDAR_CFG, SDAR_MIX
    if step == "conv" or not metric.startswith("flash_bd_"):
        assert _read(metric, run) is None        # no op under the scope


# -- the toy cell through the harness -----------------------------------------


@pytest.fixture(scope="module")
def tiny_sdar_root(tmp_path_factory):
    return benchmark_tiny_sdar.make(str(tmp_path_factory.mktemp("bench")))


def test_tiny_sdar_cell_runs_end_to_end(tiny_sdar_root, world, capsys):
    """Three arrays a row from the generator through ``ShardedLoader``, the
    noising, the doubled sequence under the block-diffusion mask, the
    routed experts (held 2..5 of 8) and the masked loss through
    ``run_cell`` as the chip's cell goes."""
    result = _run_cell(tiny_sdar_root, "tiny-sdar", 1)
    _well_formed(result, "tiny-sdar", 1)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out = capsys.readouterr().out
    for name in ("loss_gap", "grad_norm_gap", "grad_sketch_gap",
                 "update_norm_gap", "final_loss", "nonfinite_losses"):
        assert f"check: {name} = " in out and "limit" in out


def _rows_left_out(step):
    """A timed path that trains on the first row of each chip's two."""
    def broken(state, x, y):
        half = lambda t: tuple(jnp.concatenate([a[:1], a[:1]])  # noqa: E731
                               for a in t)
        return step(state, half(x), half(y))
    return broken


def test_tiny_sdar_cell_with_rows_left_out_is_not_correct(
        tiny_sdar_root, world, capsys):
    result = _run_cell(tiny_sdar_root, "tiny-sdar", 1,
                       break_step=_rows_left_out)
    assert result["correct"] is False
    assert any(line.startswith("check: loss_gap = ") and "OVER" in line
               for line in capsys.readouterr().out.splitlines())


def test_tiny_sdar_adds_files_and_entries_and_edits_none(tiny_sdar_root,
                                                         tmp_path):
    plain = benchmark_tiny.make(str(tmp_path))
    added = set()
    for sub in ("configs", "traffic", "layer_metrics"):
        had = set(os.listdir(os.path.join(plain, "benchmarks", sub)))
        now = set(os.listdir(os.path.join(tiny_sdar_root, "benchmarks", sub)))
        assert had <= now
        added |= {f"{sub}/{f}" for f in now - had}
    assert added == {"configs/sdar_tiny.json", "configs/sdar_tiny.py",
                     "traffic/seq64-b2-bd4.json"}


# -- the two cells in ``BENCHMARK.json`` -------------------------------------------
# (which accepted readers list them is ``test_benchmark_lists.py``'s)


def test_the_two_cells_report_their_readers_and_gpt2s_4k_is_gpt2s_16ks_kind():
    spec = Spec(benchmark_tiny.REPO)
    mine = spec.cell("sdar-bd4-8k")
    assert mine.end_to_end == ["tokens_per_s_chip", "mfu", "setup_s"]
    assert set(NEW_READERS) <= set(mine.per_layer)
    mid, long = spec.cell("gpt2s-4k"), spec.cell("gpt2s-16k")
    assert mid.end_to_end == long.end_to_end == [
        "tokens_per_s_chip", "mfu", "setup_s"]      # no tail: 200 steps
    assert set(mid.per_layer) == set(long.per_layer)
    assert mid.config == long.config == "gpt2_small"
    assert mid.adapter.limits(mid.cfg, mid.mix) \
        == long.adapter.limits(long.cfg, long.mix)
    # 968.4 MFLOP a token at 4096: 6 N + 12 L s d / 2
    assert math.isclose(mid.adapter.flops_per_item(mid.cfg, mid.mix),
                        6 * 123_653_376 + 12 * 12 * 4096 * 768 / 2)
