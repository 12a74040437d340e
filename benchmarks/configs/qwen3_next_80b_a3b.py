"""Qwen3-Next-80B-A3B, one chip's share: how the benchmark builds it from
the program, its reference, what a token costs, and the limits ``correct``
holds it to.

Sizes are in ``qwen3_next_80b_a3b.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import qwen3_next_parts as parts
from benchmarks.references import qwen3_next

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, from two readings on the
#: chip at the cell's own size (``benchmarks/calibrate.py``; every reading
#: is in PERF.md section 2, "Limits of correct"): the largest value sound
#: runs of the program gave over the seeds, and the smallest the float8
#: control gave.
LIMITS = {
    # "This tree" below is the committed program (tiled expert layer,
    # learning rate 1e-4), 10 seeds; "earlier" the same model with the
    # expert layer's earlier designs, 21 seeds, 8 of them at 1e-4.
    #
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out; the precision hardly
    # moves it, so three times the sound runs' largest (this tree 1.31e-4,
    # earlier 1.09e-4; the control reads 1.7e-4 to 6.6e-4 at this learning
    # rate and may pass this one).
    "loss_gap": 4e-4,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size.  Three times the sound
    # runs' largest (earlier 0.0103, this tree 0.0061; the control reads
    # 0.020 to 0.040: rounding hardly moves a norm, so it may pass).
    "grad_norm_gap": 0.031,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs
    # read 0.070 to 0.088 (this tree; earlier 0.065 to 0.082) — seven times
    # GPT-2's, nearly all of it the experts' leaves, where 4% of the top-10
    # picks of a layer differ between bf16 activations and the float32
    # reference — and the control 0.477 to 0.532 (6 seeds): the geometric
    # middle.
    "grad_sketch_gap": 0.2,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged (which reads 1.0); three times the sound runs' largest
    # (earlier 0.00121, this tree 0.00087; the control reads 0.0014 to
    # 0.0049).
    "update_norm_gap": 0.0036,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The labels are uniform over the sliced vocabulary, so no model can get
#: under ln(vocab) except by memorising the dataset.
FINAL_LOSS_OVER_LN_VOCAB = 0.5
#: tokens of the sample ``init_train_state`` runs the model on, eagerly: no
#: parameter's shape depends on the sequence, so a short one
SAMPLE_TOKENS = 1024


def _sequence(mix: dict) -> int:
    return int(mix["arrays"][0]["shape"][0])


def program(cfg: dict, mix: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.gpt import next_token_loss
    from horovod_tpu.models.qwen3_next import Qwen3Next

    model = Qwen3Next(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["num_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"],
        remat=cfg["remat"] == "decoder_layer",
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    return {
        "model": model,
        "optimizer": optax.adam(cfg["learning_rate"], b1=ADAM_B1),
        "apply_fn": lambda v, x, train=True: model.apply(v, x),
        "loss_fn": next_token_loss,
        "has_batch_stats": False,
        "sample": jnp.zeros((1, min(SAMPLE_TOKENS, _sequence(mix))),
                            jnp.int32),
        # the ids are the input and, shifted inside the loss, the labels
        "xy": lambda arrays: (arrays[0], arrays[0]),
        # after one step Adam's first moment is (1 - b1) * gradient
        "first_gradient": lambda opt_state: (opt_state[0].mu,
                                             1.0 / (1.0 - ADAM_B1)),
    }


def reference(cfg: dict, mix: dict) -> dict:
    return {
        "init": lambda seed: qwen3_next.seeded_weights(cfg, seed),
        "loss": lambda precision: qwen3_next.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
