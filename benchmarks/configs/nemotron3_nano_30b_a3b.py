"""NVIDIA-Nemotron-3-Nano-30B-A3B, one chip's share: how the benchmark
builds it from the program, its reference, what a token costs, and the
limits ``correct`` holds it to.

Sizes are in ``nemotron3_nano_30b_a3b.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.references import nemotron_h

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, each from what was read on the
#: chip at the cell's own size (my chip runs, PR 41; every reading is in
#: PERF.md section 2, "Limits of correct"): the sound program's largest (the
#: check numbers of the benchmark's own runs) and the float8 control's
#: smallest (``calibrate.py --control-only``, 3 seeds).  They were set before
#: the runs from the committed files, from the two runs this recipe had then
#: (normal 0.02 everywhere, Adam 1e-4) and the sixteen of the recipes tried
#: beside it, and stood through those runs unedited.  The sound sketch gap
#: is a third of ``kanana2_30b_a3b``'s under the same router: one attention
#: block of nine, and flat (no seeded q scale), so bfloat16's rounding of q
#: and k moves no softmax; what is left is the picks that flip between
#: bfloat16 rows and the float32 reference's.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Sound runs read 6.2e-5 and 8.9e-5 at this recipe (1.4e-5 to 4.0e-5
    # under a smaller rate or smaller down projections): three times the
    # largest.  The control read 1.8e-4 to 2.2e-4 and may pass this one.
    "loss_gap": 3e-4,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size (half the size reads 0.5).
    # Rounding moves a norm little: sound 0.0010 to 0.0043 over every
    # recipe, the control 0.0091 to 0.0201: three times the sound runs'
    # largest; the control may pass this one too.
    "grad_norm_gap": 0.013,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs
    # read 0.067 to 0.073 with 0.02 everywhere (0.042 to 0.060 with smaller
    # down projections), the control 0.206 to 0.216: the geometric middle
    # of 0.073 and 0.206 (0.123), a factor of 1.7 from either.
    "grad_sketch_gap": 0.125,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged, which reads 1.0.  Sound runs read 0.0004 to 0.0030, the
    # control 0.0019 to 0.0040: precision hardly moves it, so between the
    # largest sound reading and 1 with the more room above the reading
    # (the geometric middle is 0.055), ten times the readings.
    "update_norm_gap": 0.03,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The ids are uniform over the slice of the vocabulary, so a model that has
#: seen nothing reads ln(16 384) = 9.70; seeded at 0.02 it starts at 10.23
#: to 10.26 (half the logits' variance over) and at 1e-4 a window ends at
#: 9.78 (no row comes twice in a window or the checked steps: 73 steps, 128
#: rows; what is learnt is the logits' scale).  The limit is
#: ``kanana2_30b_a3b``'s, ln V + 2 = 11.70, held against a run that
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
    from horovod_tpu.models.nemotron_h import NemotronH

    model = NemotronH(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=nemotron_h.kinds(cfg),
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], mamba_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["n_routed_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        moe_group_rows=cfg["moe_group_rows"],
        moe_capacity_factor=cfg["moe_capacity_factor"],
        norm_eps=cfg["norm_eps"],
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
        "init": lambda seed: nemotron_h.seeded_weights(cfg, seed),
        "loss": lambda precision: nemotron_h.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
