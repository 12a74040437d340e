"""ResNet family (flax/linen), TPU-first.

The reference has no model code at all — its benchmarks instantiate Keras
``applications.ResNet50`` (reference
examples/tensorflow2_synthetic_benchmark.py:64) and tf_cnn_benchmarks
(docs/benchmarks.rst:15-63).  This module provides the equivalent model
family natively so the framework's headline benchmark (ResNet-50 synthetic,
BASELINE.md) is self-contained.

TPU-first choices:

* **NHWC** layouts and 3x3/1x1 convs that XLA tiles directly onto the MXU;
* **bf16 compute, f32 params** (``dtype``/``param_dtype`` split) — the MXU's
  native mixed precision, no loss scaling needed;
* BatchNorm statistics are per-replica (Horovod-style data parallelism does
  not sync BN; cross-replica stats would add per-step collectives);
* no Python control flow in the forward pass — fully unrollable for jit.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax.numpy as jnp
from flax import linen as nn
from jax import lax

ModuleDef = Any


class SpaceToDepthConvInit(nn.Module):
    """The ResNet stem (7x7 stride-2 conv) computed as a 4x4 stride-1
    conv on space-to-depth-transformed input — mathematically identical
    output, but the MXU sees 12 input channels instead of 3 and no
    stride (the MLPerf TPU ResNet trick).  Holds the SAME (7,7,Cin,F)
    kernel parameter as the plain conv, so checkpoints interchange;
    the 4x4x(4Cin) kernel is derived in-graph (tiny, XLA folds it)."""

    features: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        if h % 2 or w % 2:
            raise ValueError(
                f"space_to_depth stem needs even spatial dims, got "
                f"{(h, w)}; use stem='conv' for odd input sizes"
            )
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (7, 7, c, self.features), self.param_dtype,
        ).astype(self.dtype)
        x = x.astype(self.dtype)
        # space-to-depth(2): y[p,q,(a,b,ch)] = x[2p+a, 2q+b, ch]
        y = x.reshape(b, h // 2, 2, w // 2, 2, c) \
             .transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        # out(i,j) = sum_{u,v} x[u,v] K[u-2i+3, v-2j+3]; with u=2p+a the
        # kernel index is 2(p-i)+a+3 = 2P+a-1 for P=p-i+2 in [0,4) — pad
        # one leading zero row/col so it becomes K8[2P+a, 2Q+b]
        k8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        kp = k8.reshape(4, 2, 4, 2, c, self.features) \
               .transpose(0, 2, 1, 3, 4, 5) \
               .reshape(4, 4, 4 * c, self.features)
        return lax.conv_general_dilated(
            y, kp, (1, 1), [(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3), strides=(self.strides,) * 2)(y)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=(self.strides,) * 2,
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides,) * 2)(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), strides=(self.strides,) * 2,
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    stem: str = "conv"  # "conv" | "space_to_depth" (same params/output)

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(
            nn.Conv, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, param_dtype=self.param_dtype,
        )
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = SpaceToDepthConvInit(
                features=self.num_filters, dtype=self.dtype,
                param_dtype=self.param_dtype, name="conv_init",
            )(x)
        elif self.stem == "conv":
            x = conv(self.num_filters, (7, 7), strides=(2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        else:
            raise ValueError(
                f"unknown stem {self.stem!r} (want 'conv' or "
                "'space_to_depth')"
            )
        x = nn.relu(norm(name="bn_init")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(
                    filters=self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=self.param_dtype)(x)
        # logits in f32 for a numerically stable softmax/loss
        return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)

MODELS = {
    "ResNet18": ResNet18,
    "ResNet34": ResNet34,
    "ResNet50": ResNet50,
    "ResNet101": ResNet101,
    "ResNet152": ResNet152,
}
