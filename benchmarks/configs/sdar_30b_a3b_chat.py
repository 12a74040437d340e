"""SDAR-30B-A3B-Chat trained by block diffusion, one chip's share: how the
benchmark builds it from the program, its reference, what a data token
costs, and the limits ``correct`` holds it to.

Sizes are in ``sdar_30b_a3b_chat.json``; nothing here is a size.  A row of
the traffic is three arrays (ids, the blocks' noise levels, the tokens'
draws); the model takes them as one tuple and so does the loss.
"""

from __future__ import annotations

import math

from benchmarks.harness import sdar_parts as parts
from benchmarks.references import sdar

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, from two readings on the
#: chip at the cell's own size (my chip runs, PR 30; every reading is in
#: PERF.md section 2, "Limits of correct"): the largest sound runs of the
#: program gave (the check numbers of the benchmark's own runs: 11 seeds of
#: calls I and J under the experts' load bound, and 9 of calls F and H
#: before it), and the smallest the float8 control gave (``calibrate.py
#: --control-only``: 3 seeds of call I, read after ``grad_sketch_gap``'s
#: limit, the one the control has to fail, was committed, and 2 of call F).  With the heads' q and k norms seeded at 2 the scores' deviation is
#: 4, and a softmax that sharp shows bfloat16's rounding: the program in
#: float32 agrees with the reference to 1e-5, in bfloat16 1.2% of layer 0's
#: picks and 8.8% of layer 3's flip (PERF.md section 6), so every gap is
#: several times what it read at unit norms.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out.  Sound runs read 3.5e-4 to
    # 1.81e-3 (20 seeds), the control 1.25e-3 to 5.6e-3 (5): they overlap,
    # so three times the sound runs' largest; the control may pass this
    # one.
    "loss_gap": 5.5e-3,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it: a gradient of the wrong size.  Rounding hardly moves a
    # norm (sound 0.0066 to 0.070, the largest in call J, a layer-0 q norm
    # weight; the control 0.048 to 0.226), so three times the sound runs'
    # largest: a gradient of half the size reads 0.5; the control may pass
    # this one.
    "grad_norm_gap": 0.21,
    # Mean over the leaves of the difference between the first gradient's
    # sketches: the number the lower precision has to fail.  Sound runs
    # read 0.237 to 0.276 (20 seeds), the control 0.963 to 1.050 (5): the
    # geometric middle (0.52), a factor of 1.9 from either; all five
    # control seeds fail it.
    "grad_sketch_gap": 0.5,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged (which reads 1.0).  Sound runs read 0.0009 to 0.0082 (and
    # once 0.048 under unit q / k norms: an expert's matrix whose rows the
    # routers moved between the three steps), the control 0.0020 to
    # 0.0095: far under 1, with the more room above the readings.
    "update_norm_gap": 0.15,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The ids are uniform over the data's slice of the vocabulary and a masked
#: token's weight 1 / t has mean 1 over the tokens, so a model that has seen
#: nothing reads ln(vocab) = 9.85.  No row comes twice in a window (41
#: steps, 64 rows), so nothing is learnt, and Adam at 1e-4 on a loss whose
#: weights reach 16 fits each row it has seen: runs end at 10.13 to 10.37
#: (0.52 over; 9.93 to 10.17 under unit q / k norms; 10.22 to 10.29 under the
#: load bound).  The accepted cells'
#: 0.5 leaves that no room and ``check.py`` wants a limit for every number,
#: so this one is held against a run that diverges, with three times the
#: largest reading's room and more (PERF.md section 7 asks for the repair).
FINAL_LOSS_OVER_LN_VOCAB = 2.0
#: tokens of the sample ``init_train_state`` runs the model on, eagerly: no
#: parameter's shape depends on the sequence, so a short one
SAMPLE_TOKENS = 1024


def _sequence(mix: dict) -> int:
    return int(mix["arrays"][0]["shape"][0])


def program(cfg: dict, mix: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.sdar import SDAR, block_diffusion_loss

    model = SDAR(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["num_experts"],
        router_experts=cfg["router_num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_group_rows=cfg["moe_group_rows"],
        moe_capacity_factor=cfg["moe_capacity_factor"],
        qk_norm_init=cfg["qk_norm_init"],
        rms_norm_eps=cfg["rms_norm_eps"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        remat=cfg["remat"] == "decoder_layer",
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    tokens = min(SAMPLE_TOKENS, _sequence(mix))
    return {
        "model": model,
        "optimizer": optax.adam(cfg["learning_rate"], b1=ADAM_B1),
        "apply_fn": lambda v, x, train=True: model.apply(v, x),
        "loss_fn": block_diffusion_loss,
        "has_batch_stats": False,
        "sample": (jnp.zeros((1, tokens), jnp.int32),
                   jnp.ones((1, tokens // cfg["block_length"]), jnp.int32),
                   jnp.zeros((1, tokens), jnp.int32)),
        # the three arrays of a row are the input and, with the ids as the
        # targets and the noise as the weights, the labels
        "xy": lambda arrays: (tuple(arrays), tuple(arrays)),
        # after one step Adam's first moment is (1 - b1) * gradient
        "first_gradient": lambda opt_state: (opt_state[0].mu,
                                             1.0 / (1.0 - ADAM_B1)),
    }


def reference(cfg: dict, mix: dict) -> dict:
    return {
        "init": lambda seed: sdar.seeded_weights(cfg, seed),
        "loss": lambda precision: sdar.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per data token."""
    return parts.train_flops_per_token(cfg, _sequence(mix))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
