"""ResNet-50 v1.5: how the benchmark builds it from the program, its
reference, what an image costs, and the limits ``correct`` holds it to.

Sizes are in ``resnet50.json``; nothing here is a size.
"""

from __future__ import annotations

import math

from benchmarks.harness import flops
from benchmarks.references import resnet50

#: As in gpt2_small.py, from readings on the chip at the cell's own size
#: (PERF.md section 2, "Limits of correct").
LIMITS = {
    # three times the sound runs' largest (2.18e-5 over 13 seeds); the
    # control reads 4.6e-5 to 2.0e-4 (4 seeds) and passes on one of them
    "loss_gap": 6.5e-5,
    # every one of the 161 leaves has a first gradient (LAST_SCALE in the
    # reference): bfloat16 moves the worst leaf's norm by up to 5.7% and
    # float8 by 6-21%.  Held against a gradient of the wrong size, at three
    # times the sound runs' largest; a halved kernel gradient reads 0.5
    "grad_norm_gap": 0.17,
    # the number that tells the lower precision: sound runs read
    # 0.171-0.191 (13 seeds), the control 0.515-0.560 (4); the limit is the
    # geometric middle
    "grad_sketch_gap": 0.31,
    # three times the sound runs' largest (0.0455); a step that returns its
    # state unchanged reads 1.0
    "update_norm_gap": 0.14,
    "nonfinite_losses": 0.0,
    "batch_shards_missing": 0.0,
    "state_leaves_not_replicated": 0.0,
}
#: Uniform random labels: the loss cannot settle under ln(classes) and a
#: sound run stays near it.
FINAL_LOSS_OVER_LN_CLASSES = 0.5


def program(cfg: dict, mix: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet

    from horovod_tpu.models import resnet as resnet_mod

    dtype = jnp.dtype(cfg["compute_dtype"])
    model = ResNet(
        stage_sizes=cfg["stage_sizes"],
        block_cls=resnet_mod.BottleneckBlock,
        num_classes=cfg["num_classes"], num_filters=cfg["num_filters"],
        dtype=dtype, param_dtype=jnp.dtype(cfg["param_dtype"]))
    size = int(cfg["image_size"])

    def apply_fn(variables, images, train=True, **kw):
        # the accelerator's end of every image pipeline: rows of bytes in
        # (a flat row copies to the device as it lies; an NHWC uint8 array
        # is re-tiled on the host first, 3 channels to a 128-wide tile),
        # shaped, cast and scaled on the device
        x = images.reshape(-1, size, size, 3).astype(dtype) * (1.0 / 255.0)
        return model.apply(variables, x, train=train, **kw)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    return {
        "model": model,
        "optimizer": optax.sgd(cfg["learning_rate"],
                               momentum=cfg["momentum"]),
        "apply_fn": apply_fn,
        "loss_fn": loss_fn,
        "has_batch_stats": True,
        "sample": jnp.zeros((2, size, size, 3), jnp.float32),
        "xy": lambda arrays: (arrays[0], arrays[1]),
        # after one step the momentum trace is the gradient itself
        "first_gradient": lambda opt_state: (opt_state[0].trace, 1.0),
    }


def reference(cfg: dict, mix: dict) -> dict:
    return {
        "init": lambda seed: resnet50.seeded_weights(cfg, seed),
        "loss": lambda precision: resnet50.loss_fn(cfg, precision),
        "optimizer": cfg["optimizer"],
        "lr": cfg["learning_rate"],
    }


def flops_per_item(cfg: dict, mix: dict) -> float:
    """Per image."""
    return flops.resnet50_train_flops_per_image(cfg,
                                                int(cfg["image_size"]))


def limits(cfg: dict, mix: dict) -> dict:
    return {**LIMITS, "final_loss": math.log(cfg["num_classes"])
            + FINAL_LOSS_OVER_LN_CLASSES}
