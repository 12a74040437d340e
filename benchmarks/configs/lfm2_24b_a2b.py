"""LFM2-24B-A2B, one chip's share: how the benchmark builds it from the
program, its reference, what a token costs, and the limits ``correct``
holds it to.

Sizes are in ``lfm2_24b_a2b.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import lfm2_parts as parts
from benchmarks.references import lfm2

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, each from what was read on the
#: chip at the cell's own size (my chip runs, PR 45, calls 1 to 3; every
#: reading is in PERF.md section 2, "Limits of correct"): the sound program's
#: largest (``calibrate.py``, 6 seeds, and the check numbers of the
#: benchmark's own runs, 10 seeds) and the float8 control's smallest
#: (``calibrate.py --control-only``, 3 seeds).  Set after call 2, before the
#: eight runs of call 3, which ran under them; ``loss_gap`` alone was then
#: set again, from 1.7e-4, when call 3 read a larger sound value than the
#: first eight seeds had.  The sound sketch gap lies between
#: ``nemotron3_nano_30b_a3b``'s (0.07: one flat attention block of nine) and
#: ``kanana2_30b_a3b``'s (0.23: five attention layers at a seeded
#: temperature): two attention layers of seven whose q and k head norms
#: start at 2.0, so bfloat16's rounding of q and k moves a softmax of
#: deviation 4, and the rows that differ route differently from there on.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out.  Sound runs read 1.7e-5 to
    # 7.4e-5 (16 seeds; 5.7e-5 at most on the first eight), the control
    # 1.9e-4 to 2.8e-4: three times the sound runs' largest; the control may
    # pass this one (it fails it on two seeds of three).
    "loss_gap": 2.2e-4,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size (half the size reads 0.5).
    # Rounding moves a norm little: sound 0.0019 to 0.0046 (16 seeds; worst
    # leaf a q or k head norm's weight on most), the control 0.0111 to
    # 0.0293: three times the sound runs' largest; the control may pass this
    # one (it fails it on two seeds of three).
    "grad_norm_gap": 0.014,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs read
    # 0.1151 to 0.1362 (16 seeds; 0.1343 at most on the first eight), the
    # control 0.5012 to 0.5229: the geometric middle of 0.1343 and 0.5012
    # (0.2594), a factor of 1.9 from either side's readings; all three
    # control seeds fail it.
    "grad_sketch_gap": 0.26,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged, which reads 1.0.  Sound runs read 0.00017 to 0.00064 (worst
    # leaf a router's ``gate`` or an expert matrix of the last layers), the
    # control 0.00049 to 0.00067: precision hardly moves it, so between the
    # largest sound reading and 1 with the more room above the reading (the
    # geometric middle is 0.025): ``kanana2_30b_a3b``'s 0.03, 47 times the
    # readings.
    "update_norm_gap": 0.03,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The ids are uniform over the slice of the vocabulary, so a model that has
#: seen nothing reads ln(8192) = 9.01; seeded at 0.02 with the table tied to
#: the head it starts at 9.41 to 9.43 and at 1e-4 a window of 51 steps ends
#: at 9.414 to 9.419 (no row comes twice in a window or the checked steps:
#: 256 rows), 0.41 over ln(vocabulary).  The limit is ``kanana2_30b_a3b``'s,
#: ln V + 2 = 11.01, five times the reading's room, held against a run that
#: diverges: a sound run never ends above its start.
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
    from horovod_tpu.models.lfm2 import Lfm2

    model = Lfm2(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=lfm2.kinds(cfg),
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        conv_taps=cfg["conv_L_cache"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=lfm2.head_dim(cfg),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        num_experts=cfg["num_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_group_rows=cfg["moe_group_rows"],
        moe_capacity_factor=cfg["moe_capacity_factor"],
        qk_norm_init=cfg["qk_norm_init"], norm_eps=cfg["norm_eps"],
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
        "init": lambda seed: lfm2.seeded_weights(cfg, seed),
        "loss": lambda precision: lfm2.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
