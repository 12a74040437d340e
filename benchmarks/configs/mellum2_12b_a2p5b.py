"""Mellum2-12B-A2.5B-Instruct, one chip's share: how the benchmark builds it
from the program, its reference, what a token costs, and the limits
``correct`` holds it to.

Sizes are in ``mellum2_12b_a2p5b.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import mellum2_parts as parts
from benchmarks.references import mellum2

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, each from two readings on the
#: chip at the cell's own size with the q projections seeded at 0.0807 (my
#: chip runs, PR 38, calls Q, C and D; every reading is in PERF.md section
#: 2, "Limits of correct"): the largest the sound program gave (the check
#: numbers of the benchmark's own runs, 17 seeds) and the smallest the
#: float8 control gave (``calibrate.py --control-only``, 3 seeds).  The
#: window layers' scores have deviation 3.7 and the full layer's 6.1, and a
#: softmax that sharp shows bfloat16's rounding of q and k as it does in
#: ``sdar_30b_a3b_chat`` and ``kanana2_30b_a3b`` (PERF.md section 2): the
#: program in float32 agrees with the reference to 1e-5
#: (tests/test_mellum2.py), in bfloat16 the rows differ by a few percent and
#: route differently from the next layer on.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out.  Sound runs read 9.1e-5 to
    # 6.4e-4 (17 seeds; 3.8e-4 at most until call D's traced seed), the
    # control 7.8e-4 to 1.48e-3: three times the sound runs' largest; the
    # control passes this one.
    "loss_gap": 2.0e-3,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size (half the size reads 0.5).
    # Rounding hardly moves a norm: sound 0.0028 to 0.0151, the control
    # 0.0176 to 0.0288, so three times the sound runs' largest; the control
    # passes this one.
    "grad_norm_gap": 0.046,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs
    # read 0.251 to 0.292 as it was set (0.311 at most over 17 seeds), the
    # control 0.971 to 1.021: the geometric middle (0.532), a factor of 1.7
    # to 1.8 from either; all three control seeds fail it.
    "grad_sketch_gap": 0.53,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged, which reads 1.0.  Precision hardly moves it (sound 0.00022
    # to 0.00045, the control 0.0007 to 0.0011): the geometric middle of
    # the largest sound reading and 1, nearly fifty times the room above
    # the readings.
    "update_norm_gap": 0.021,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The ids are uniform over the slice of the vocabulary, so a model that has
#: seen nothing reads ln(12 288) = 9.42; seeded at 0.02 it starts at 9.87 to
#: 9.89.  No row comes twice in a window (58 steps and the three checked
#: ones, 128 rows), so nothing is memorised: runs end at 9.863 to 9.878,
#: 0.46 over ln(vocabulary).  The accepted cells' 0.5 would leave that a
#: tenth of its own room and ``check.py`` wants a limit for every number,
#: so this one is ``kanana2_30b_a3b``'s, held against a run that diverges,
#: with over three times the largest reading's room (PERF.md section 7 asks
#: for the repair).
FINAL_LOSS_OVER_LN_VOCAB = 2.0
#: tokens of the sample ``init_train_state`` runs the model on, eagerly: no
#: parameter's shape depends on the sequence, so a short one
SAMPLE_TOKENS = 1024


def _sequence(mix: dict) -> int:
    return int(mix["arrays"][0]["shape"][0])


def program(cfg: dict, mix: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.gpt import next_token_loss
    from horovod_tpu.models.mellum2 import Mellum2

    rope = cfg["rope_parameters"]
    window, full = rope["sliding_attention"], rope["full_attention"]
    if window["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or window["rope_theta"] != full["rope_theta"]:
        raise ValueError("the adapter passes the program a default table "
                         "for the window layers and YaRN's for the full "
                         f"ones at one theta, not {rope}")
    model = Mellum2(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_positions=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=full["attention_factor"],
        num_experts=cfg["num_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_group_rows=cfg["moe_group_rows"],
        moe_capacity_factor=cfg["moe_capacity_factor"],
        q_init_std=cfg.get("q_proj_initializer_range",
                           cfg["initializer_range"]),
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
        "init": lambda seed: mellum2.seeded_weights(cfg, seed),
        "loss": lambda precision: mellum2.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
