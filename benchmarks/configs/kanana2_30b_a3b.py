"""kanana-2-30b-a3b-instruct-2601, one chip's share: how the benchmark
builds it from the program, its reference, what a token costs, and the
limits ``correct`` holds it to.

Sizes are in ``kanana2_30b_a3b.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import kanana2_parts as parts
from benchmarks.references import kanana2

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, each between what was read on
#: the chip at the cell's own size (my chip runs, PR 34, calls A, B and C; every
#: reading is in PERF.md section 2, "Limits of correct"): the largest the
#: sound program gave (the check numbers of the benchmark's own runs, 18
#: seeds) and the smallest the float8 control gave (``calibrate.py
#: --control-only``, 3 seeds).  The q projections' seeded deviation 0.146
#: makes the scores' deviation 4.2, and a softmax that sharp shows bfloat16's
#: rounding of q and k as it does in ``sdar_30b_a3b_chat`` (PERF.md section
#: 2): the program in float32 agrees with the reference to 1e-5
#: (tests/test_kanana2.py), in bfloat16 the rows differ by a few percent
#: and route differently from the next layer on.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out.  Sound runs read 3.8e-5 to
    # 3.60e-4, the control 4.4e-4 to 1.37e-3: three times the sound runs'
    # largest; the control may pass this one.
    "loss_gap": 1.1e-3,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size (half the size reads 0.5).
    # Rounding hardly moves a norm: sound 0.0039 to 0.0281, the control
    # 0.0141 to 0.0548, they overlap, so three times the sound runs' largest;
    # the control may pass this one.
    "grad_norm_gap": 0.085,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs
    # read 0.196 to 0.233, the control 0.933 to 0.979: the geometric middle
    # (0.466), a factor of 2.0 from either; all three control seeds fail it.
    "grad_sketch_gap": 0.47,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged, which reads 1.0.  Sound runs read 0.00040 to 0.00101, the
    # control 0.00099 to 0.0022: the geometric middle of the largest sound
    # reading and 1, thirty times the room above the readings.
    "update_norm_gap": 0.03,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The ids are uniform over the slice of the vocabulary, so a model that has
#: seen nothing reads ln(16 032) = 9.68; seeded at 0.02 it starts at 10.06
#: to 10.11.  No row comes twice in a window (62 steps and the three checked
#: ones, 64 rows), so nothing is memorised as in ``qwen3next-8k`` (88 steps):
#: runs end at 9.94 to 10.09, 0.41 over ln(vocabulary) at most.  The accepted
#: cells' 0.5 leaves that a quarter of its own room and ``check.py`` wants
#: a limit for every number, so this one is ``sdar_30b_a3b_chat``'s, held
#: against a run that diverges, with over three times the largest reading's
#: room (PERF.md section 7 asks for the repair).
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
    from horovod_tpu.models.kanana2 import Kanana2

    model = Kanana2(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_dense_layers=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["n_routed_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
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
        "init": lambda seed: kanana2.seeded_weights(cfg, seed),
        "loss": lambda precision: kanana2.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
