"""GPT-2-small: how the benchmark builds it from the program, its
reference, what a token costs, and the limits ``correct`` holds it to.

Sizes are in ``gpt2_small.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import flops
from benchmarks.references import gpt2

ADAM_B1 = 0.9

#: Limits of the numbers ``correct`` compares, from two readings on the chip
#: at the cells' own sizes (``benchmarks/calibrate.py``; every reading is in
#: PERF.md section 2, "Limits of correct"): the largest value sound runs of
#: the program gave over the seeds, and the smallest the float8 control
#: gave.  One set serves the three GPT cells.
LIMITS = {
    # |program - reference| / reference, worst of the checked steps' losses.
    # Held against a part of the batch left out; three times the sound
    # runs' largest (1.98e-5, gpt2s-16k).
    "loss_gap": 6e-5,
    # Worst leaf, the gap between the norms of the first gradient as Adam
    # receives it.  Rounding noise hardly moves a norm (the control reads
    # 0.011-0.018), so this is held against a gradient of the wrong size —
    # an exchange or part of the batch left out — at three times the sound
    # runs' largest (0.0076, gpt2s-16k).
    "grad_norm_gap": 0.023,
    # Mean over the leaves of the difference between the first gradient's
    # sketches, an estimate of the norm of the gradients' difference: the
    # number the lower precision has to fail.  Sound runs read at most
    # 0.0111 (16 seeds over two cells), the control at least 0.076 (6).
    "grad_sketch_gap": 0.03,
    # Worst leaf, the gap between the norms of the parameters' change over
    # the checked steps.  Held against a step that returns its state
    # unchanged (which reads 1.0); three times the sound runs' largest
    # (0.0072, gpt2s-1k).
    "update_norm_gap": 0.022,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: The labels are uniform over the vocabulary, so no model can get under
#: ln(vocab) except by memorising the dataset; a sound run ends just above
#: it and a diverging one far above.
FINAL_LOSS_OVER_LN_VOCAB = 0.5


def _sequence(mix: dict) -> int:
    return int(mix["arrays"][0]["shape"][0])


def _positions(cfg: dict, mix: dict) -> int:
    return max(_sequence(mix), int(cfg["n_positions"]))


def program(cfg: dict, mix: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.gpt import gpt2_small, next_token_loss

    model = gpt2_small(
        vocab_size=cfg["vocab_size"], hidden_dim=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        mlp_dim=cfg["n_inner"], max_len=_positions(cfg, mix),
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    return {
        "model": model,
        "optimizer": optax.adam(cfg["learning_rate"], b1=ADAM_B1),
        "apply_fn": lambda v, x, train=True: model.apply(v, x),
        "loss_fn": next_token_loss,
        "has_batch_stats": False,
        "sample": jnp.zeros((2, _sequence(mix)), jnp.int32),
        # the ids are the input and, shifted inside the loss, the labels
        "xy": lambda arrays: (arrays[0], arrays[0]),
        # after one step Adam's first moment is (1 - b1) * gradient
        "first_gradient": lambda opt_state: (opt_state[0].mu,
                                             1.0 / (1.0 - ADAM_B1)),
    }


def reference(cfg: dict, mix: dict) -> dict:
    positions = _positions(cfg, mix)
    return {
        "init": lambda seed: gpt2.seeded_weights(cfg, positions, seed),
        "loss": lambda precision: gpt2.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per token."""
    return flops.transformer_train_flops_per_token(
        flops.gpt2_matmul_params(cfg), cfg["n_layer"], cfg["n_embd"],
        _sequence(mix), causal=True)


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["vocab_size"])
            + FINAL_LOSS_OVER_LN_VOCAB}
