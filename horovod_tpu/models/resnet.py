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


class PallasConvBN3x3(nn.Module):
    """Fused stride-1 3x3 conv + BatchNorm + ReLU over the Pallas kernels
    (ops/conv_bn.py): train mode runs the conv+stats-epilogue kernel with
    the full-BN-backward custom VJP; eval mode runs the folded-affine
    kernel.  The round-4 conv+BN experiment module (root PERF.md) —
    selected by ``ResNet(conv_bn="pallas")``; its parameter layout is its
    own (kernel/scale/bias + batch_stats mean/var), so checkpoints do NOT
    interchange with the (Conv, BatchNorm) pair it replaces."""

    features: int
    train: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        from ..ops.conv_bn import conv3x3_bn_relu, conv3x3_bn_relu_train

        cin = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (3, 3, cin, self.features), self.param_dtype,
        )
        gamma = self.param("scale", nn.initializers.ones,
                           (self.features,), self.param_dtype)
        beta = self.param("bias", nn.initializers.zeros,
                          (self.features,), self.param_dtype)
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda: jnp.zeros((self.features,), jnp.float32))
        ra_var = self.variable(
            "batch_stats", "var",
            lambda: jnp.ones((self.features,), jnp.float32))
        k = kernel.astype(self.dtype)
        x = x.astype(self.dtype)
        if self.train:
            out, mean, var = conv3x3_bn_relu_train(
                x, k, gamma.astype(jnp.float32), beta.astype(jnp.float32),
                self.epsilon,
            )
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        else:
            scale = gamma * (lax.rsqrt(ra_var.value + self.epsilon))
            bias = beta - ra_mean.value * scale
            out = conv3x3_bn_relu(x, k, scale, bias)
        return out


class BatchNormReLU(nn.Module):
    """BatchNorm + ReLU with the elementwise apply fused into one Pallas
    pass (ops/elementwise.py ``scale_bias_relu``) — the compute tier's
    norm+activation join, selected by ``ResNet(norm_act="pallas")``.

    The per-channel statistics (a tiny reduction XLA handles well) and
    the folded ``scale``/``bias`` stay in jnp; the [B,H,W,C]-sized
    normalize+activate traffic — the HBM-bound part — runs as the single
    fused kernel.  Gradients flow through batch mean/var exactly like
    ``flax.linen.BatchNorm`` (the folded affine is a function of the
    batch stats, so autodiff chains the kernel's dscale/dbias back
    through them).  Parameter names inside the module mirror
    ``BatchNorm``'s (params scale/bias, batch_stats mean/var), but the
    module path differs — like ``conv_bn="pallas"``, checkpoints do NOT
    interchange with the pair it replaces."""

    use_running_average: bool
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        from ..ops.elementwise import scale_bias_relu

        c = x.shape[-1]
        gamma = self.param("scale", nn.initializers.ones, (c,),
                           self.param_dtype)
        beta = self.param("bias", nn.initializers.zeros, (c,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((c,), jnp.float32))
        x = x.astype(self.dtype)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = xf.mean(axis=axes)
            var = jnp.maximum(
                (xf * xf).mean(axis=axes) - mean * mean, 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * \
                    lax.stop_gradient(mean)
                ra_var.value = m * ra_var.value + (1 - m) * \
                    lax.stop_gradient(var)
        scale = gamma.astype(jnp.float32) * lax.rsqrt(var + self.epsilon)
        bias = beta.astype(jnp.float32) - mean * scale
        return scale_bias_relu(x, scale, bias)


def _norm_relu(norm, norm_relu, y):
    """Every ``norm()(y); relu(y)`` pair in the blocks goes through
    here: XLA's own elementwise fusion by default, or the single-pass
    Pallas norm+activation join when a ``BatchNormReLU`` partial is
    wired in (``norm_act="pallas"``)."""
    if norm_relu is not None:
        return norm_relu()(y)
    return nn.relu(norm()(y))


def _residual_join(residual, y, kind: str):
    """The block output ``relu(residual + y)``: XLA elementwise fusion by
    default, or the Pallas single-pass kernel (the root PERF.md 56×56
    experiment — measured by scripts/pallas_residual_experiment.py)."""
    if kind == "pallas":
        from ..ops.elementwise import residual_relu

        return residual_relu(residual, y)
    return nn.relu(residual + y)


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    join: str = "xla"  # "xla" | "pallas"
    fused: ModuleDef = None  # PallasConvBN3x3 partial (conv_bn="pallas")
    norm_relu: ModuleDef = None  # BatchNormReLU partial (norm_act="pallas")

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = _norm_relu(self.norm, self.norm_relu, y)
        if self.fused is not None and self.strides == 1:
            # the 3x3+BN+ReLU as one fused Pallas op (stride-1 blocks;
            # stride-2 stage entries keep the XLA pair)
            y = self.fused(features=self.filters)(y)
        else:
            y = self.conv(self.filters, (3, 3),
                          strides=(self.strides,) * 2)(y)
            y = _norm_relu(self.norm, self.norm_relu, y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=(self.strides,) * 2,
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return _residual_join(residual, y, self.join)


class BasicBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    join: str = "xla"  # "xla" | "pallas"
    fused: ModuleDef = None  # PallasConvBN3x3 partial (conv_bn="pallas")
    norm_relu: ModuleDef = None  # BatchNormReLU partial (norm_act="pallas")

    @nn.compact
    def __call__(self, x):
        residual = x
        if self.fused is not None and self.strides == 1:
            # first 3x3+BN+ReLU fused; the second conv's BN has no ReLU
            # before the join, so it stays on the XLA pair
            y = self.fused(features=self.filters)(x)
        else:
            y = self.conv(self.filters, (3, 3),
                          strides=(self.strides,) * 2)(x)
            y = _norm_relu(self.norm, self.norm_relu, y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), strides=(self.strides,) * 2,
                name="conv_proj",
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return _residual_join(residual, y, self.join)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    stem: str = "conv"  # "conv" | "space_to_depth" (same params/output)
    residual_join: str = "xla"  # "xla" | "pallas" (same math, see blocks)
    conv_bn: str = "xla"  # "xla" | "pallas" (fused 3x3+BN+ReLU, see blocks)
    norm_act: str = "xla"  # "xla" | "pallas" (fused BN-apply+ReLU join)

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
        fused = None
        if self.conv_bn == "pallas":
            fused = partial(
                PallasConvBN3x3, train=train, dtype=self.dtype,
                param_dtype=self.param_dtype,
            )
        elif self.conv_bn != "xla":
            raise ValueError(
                f"unknown conv_bn {self.conv_bn!r} (want 'xla' or "
                "'pallas')"
            )
        norm_relu = None
        if self.norm_act == "pallas":
            norm_relu = partial(
                BatchNormReLU, use_running_average=not train,
                dtype=self.dtype, param_dtype=self.param_dtype,
            )
        elif self.norm_act != "xla":
            raise ValueError(
                f"unknown norm_act {self.norm_act!r} (want 'xla' or "
                "'pallas')"
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
        if norm_relu is not None:
            x = norm_relu(name="bn_init")(x)
        else:
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(
                    filters=self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm,
                    join=self.residual_join, fused=fused,
                    norm_relu=norm_relu,
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
