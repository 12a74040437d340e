"""ResNet-50 v1.5 (He et al. 2015; the stride of each down-sampling block
on its 3x3 convolution) in plain float32 ``jax.numpy``: forward, softmax
cross-entropy and, through ``jax.grad``, the gradient, in training mode
with batch statistics.

Layout NHWC, kernels HWIO.  Convolutions pad as TensorFlow's "SAME" does
(a 3x3 of stride 2 on an even side pads 0 before and 1 after), which is
what the program's flax convolutions do; the 7x7 stem pads 3 and 3.  The
batch couples every row through the statistics, so the whole block of rows
goes through at once and each bottleneck is recomputed in the backward
pass to keep float32 activations of 256 images inside one chip.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import common

STAGE_BLOCKS = (3, 4, 6, 3)
BN_EPS = 1e-5


def _block_plan(cfg: dict):
    """(name, in channels, filters, stride) of the 16 bottlenecks."""
    plan, cin, i = [], cfg["num_filters"], 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        filters = cfg["num_filters"] * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            plan.append((f"BottleneckBlock_{i}", cin, filters, stride))
            cin, i = filters * 4, i + 1
    return plan


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    f0 = cfg["num_filters"]
    shapes = {"conv_init/kernel": (7, 7, 3, f0),
              "bn_init/scale": (f0,), "bn_init/bias": (f0,)}
    for name, cin, f, _ in _block_plan(cfg):
        convs = {"Conv_0": (1, 1, cin, f), "Conv_1": (3, 3, f, f),
                 "Conv_2": (1, 1, f, 4 * f)}
        norms = {"BatchNorm_0": f, "BatchNorm_1": f, "BatchNorm_2": 4 * f}
        if cin != 4 * f:
            convs["conv_proj"] = (1, 1, cin, 4 * f)
            norms["norm_proj"] = 4 * f
        for k, s in convs.items():
            shapes[f"{name}/{k}/kernel"] = s
        for k, c in norms.items():
            shapes[f"{name}/{k}/scale"] = (c,)
            shapes[f"{name}/{k}/bias"] = (c,)
    shapes["Dense_0/kernel"] = (8 * 4 * f0, cfg["num_classes"])
    shapes["Dense_0/bias"] = (cfg["num_classes"],)
    return shapes


#: What the last BatchNorm scale of every bottleneck starts at.  Not zero
#: (Goyal et al. 2017, the program's own default): every block would start
#: as the identity and the first gradient of its three convolutions and
#: first two BatchNorms would be exactly zero, in the program and in the
#: reference alike, so ``correct`` would hold the backward pass of 48 of
#: the 53 convolutions to nothing.  Not one either: gradients through 49
#: BatchNorm-ReLU layers at random weights grow by orders of magnitude
#: towards the input (Yang et al. 2019) and so does rounding error, until
#: float32 itself misses the float64 gradient by 3% and no limit could
#: tell bfloat16 from float8.  Between them every branch is open, and
#: damped (PERF.md section 2 has the readings this value was chosen from).
LAST_SCALE = 0.1


def seeded_weights(cfg: dict, seed: int) -> dict:
    """He et al.'s initialisation — normal(0, sqrt(2 / fan_in)) kernels, zero
    biases, normal(0, 0.01) for the classifier — with unit BatchNorm scales
    except the last of each bottleneck, which starts at ``LAST_SCALE``.
    Flat, ``{leaf name: array}``."""

    def rule(name, shape):
        if name == "Dense_0/kernel":
            return ("normal", 0.01)
        if name.endswith("kernel"):
            return ("normal", common.fan_in_std(shape, 2.0))
        if name.endswith("BatchNorm_2/scale"):
            return ("full", LAST_SCALE)
        return ("ones",) if name.endswith("scale") else ("zeros",)

    return common.seeded_params(param_shapes(cfg), rule, seed)


def _conv(x, kernel, stride, q, padding="SAME"):
    return lax.conv_general_dilated(
        q(x), q(kernel), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, q):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"], 1, q),
                                p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"]["kernel"], stride, q),
                                p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"], 1, q), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride, q),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, images_u8, labels)``: images — NHWC bytes, or each a
    flat row of them — scaled to [0, 1], mean softmax cross-entropy over
    the block of rows."""
    q = common.operand_rounding(precision)
    plan = _block_plan(cfg)
    size = cfg["image_size"]

    def stem(x, params):
        x = _conv(x, params["conv_init"]["kernel"], 2, q,
                  padding=[(3, 3), (3, 3)])
        x = jax.nn.relu(_batch_norm(x, params["bn_init"]))
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 [(0, 0), (1, 1), (1, 1), (0, 0)])

    def loss(params, images, labels):
        x = images.reshape(-1, size, size, 3).astype(jnp.float32) \
            * (1.0 / 255.0)
        x = jax.checkpoint(stem)(x, params)
        for name, _, _, stride in plan:
            x = jax.checkpoint(
                lambda x, p, s=stride: _bottleneck(x, p, s, q))(
                    x, params[name])
        x = jnp.mean(x, axis=(1, 2))
        logits = q(x) @ q(params["Dense_0"]["kernel"]) \
            + params["Dense_0"]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return loss
