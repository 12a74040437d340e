"""Pallas fused 3x3-conv + BatchNorm kernels — the round-4 named-lever
experiment ("custom Pallas conv+BN kernels could shave part
of the elementwise traffic" on the HBM-bound 56x56 ResNet stage).

Two variants, matching the two halves of XLA's own training-BN
structure (an earlier trace: `convert_reduce_fusion` = conv with fused
BN-stat epilogues, `multiply_add_fusion` = conv fused with BN-apply
chains):

* :func:`conv3x3_bn_relu` — conv + folded-BN affine + ReLU in one pass
  (the inference/apply shape: stats are inputs);
* :func:`conv3x3_stats` — conv emitting per-channel sum/sum-of-squares
  epilogues accumulated across the batch grid (the training-stats
  shape).

One grid step processes one image: the whole padded 56x56 input tile
lives in VMEM (~430 KB bf16 at C=64) and each of the 9 taps is a
``[H*W, Cin] @ [Cin, Cout]`` MXU matmul accumulated in f32 — the
classic shift-and-matmul conv lowering.  Measured against XLA's fused
equivalents by ``scripts/pallas_conv_bn_experiment.py``; the verdict
(positive or negative) is recorded in the root PERF.md.

Off-TPU the kernels run in interpreter mode, same policy as
ops/flash_attention.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _resolve_interpret


def _conv_taps(x_ref, w_ref, h: int, w: int, cin: int):
    """Sum of the nine shift-and-matmul taps, f32 accumulation.
    x_ref: [1, H+2, W+2, Cin] (padded); w_ref: [9*Cin, Cout]."""
    acc = None
    for dy in range(3):
        for dx in range(3):
            win = x_ref[0, dy:dy + h, dx:dx + w, :].reshape(h * w, cin)
            tap = w_ref[(dy * 3 + dx) * cin:(dy * 3 + dx + 1) * cin, :]
            t = jnp.dot(win, tap, preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
    return acc  # [H*W, Cout] f32


def _bn_relu_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref):
    h, w = o_ref.shape[1], o_ref.shape[2]
    cin = x_ref.shape[3]
    acc = _conv_taps(x_ref, w_ref, h, w, cin)
    y = acc * scale_ref[0][None, :] + bias_ref[0][None, :]
    o_ref[0] = jnp.maximum(y, 0).reshape(
        h, w, o_ref.shape[3]).astype(o_ref.dtype)


def _stats_kernel(x_ref, w_ref, o_ref, sum_ref, sq_ref):
    h, w = o_ref.shape[1], o_ref.shape[2]
    cin = x_ref.shape[3]
    acc = _conv_taps(x_ref, w_ref, h, w, cin)
    o_ref[0] = acc.reshape(h, w, o_ref.shape[3]).astype(o_ref.dtype)
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    # grid steps run sequentially on TPU: accumulate the per-channel
    # BN-stat epilogues into the shared [1, C] outputs
    sum_ref[0, :] += acc.sum(axis=0)
    sq_ref[0, :] += (acc * acc).sum(axis=0)


def _plain_kernel(x_ref, w_ref, o_ref):
    h, w = o_ref.shape[1], o_ref.shape[2]
    cin = x_ref.shape[3]
    acc = _conv_taps(x_ref, w_ref, h, w, cin)
    o_ref[0] = acc.reshape(h, w, o_ref.shape[3]).astype(o_ref.dtype)


def _pad_and_pack(x, w):
    if x.ndim != 4 or w.shape[:2] != (3, 3) or w.shape[2] != x.shape[3]:
        raise ValueError(f"need NHWC x + [3,3,Cin,Cout] w, got "
                         f"{x.shape} / {w.shape}")
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cin, cout = w.shape[2], w.shape[3]
    wp = w.reshape(9 * cin, cout)
    return xp, wp, cin, cout


def conv3x3_bn_relu(x, w, scale, bias, *,
                    interpret: Optional[bool] = None):
    """``relu(conv3x3_same(x, w) * scale + bias)`` in one Pallas pass.
    x: [B, H, W, Cin] NHWC; w: [3, 3, Cin, Cout]; scale/bias: [Cout]
    (the folded-BN affine, scale = gamma*rsqrt(var+eps))."""
    xp, wp, cin, cout = _pad_and_pack(x, w)
    b, h, wd = x.shape[0], x.shape[1], x.shape[2]
    return pl.pallas_call(
        _bn_relu_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h + 2, wd + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, wd, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, wd, cout), x.dtype),
        interpret=_resolve_interpret(interpret),
    )(xp, wp, scale.reshape(1, cout).astype(jnp.float32),
      bias.reshape(1, cout).astype(jnp.float32))


def conv3x3_stats(x, w, *, interpret: Optional[bool] = None):
    """``conv3x3_same(x, w)`` plus fused per-channel sum / sum-of-squares
    epilogues (the BN-stats half of training BN).  Returns
    ``(y [B,H,W,Cout], sum [Cout] f32, sumsq [Cout] f32)``."""
    xp, wp, cin, cout = _pad_and_pack(x, w)
    b, h, wd = x.shape[0], x.shape[1], x.shape[2]
    y, s, sq = pl.pallas_call(
        _stats_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h + 2, wd + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * cin, cout), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, wd, cout), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, wd, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(xp, wp)
    return y, s[0], sq[0]


def conv3x3_plain(x, w, *, interpret: Optional[bool] = None):
    """``conv3x3_same(x, w)`` alone (used for the transpose conv in the
    fused op's backward: stride-1 SAME conv-transpose == conv with
    spatially-flipped, io-transposed weights — no dilation)."""
    xp, wp, cin, cout = _pad_and_pack(x, w)
    b, h, wd = x.shape[0], x.shape[1], x.shape[2]
    return pl.pallas_call(
        _plain_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h + 2, wd + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9 * cin, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, wd, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, wd, cout), x.dtype),
        interpret=_resolve_interpret(interpret),
    )(xp, wp)


# ---------------------------------------------------------------------------
# Training-mode fused op: conv + batch-stats + BN-normalize + ReLU with a
# custom VJP implementing the full BatchNorm backward (gradients flow
# through mean/var, exactly like flax.linen.BatchNorm under autodiff).
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def conv3x3_bn_relu_train(x, w, gamma, beta, eps: float = 1e-5,
                          interpret: Optional[bool] = None):
    """Training forward: ``relu(BN(conv3x3_same(x, w)))`` with batch
    statistics, as one Pallas conv+stats pass plus an elementwise apply.
    Returns ``(out, batch_mean, batch_var)`` — the caller updates running
    stats from mean/var (their cotangents are treated as zero, matching
    flax's stop-gradient running-average update)."""
    out, mean, var, _ = _cbr_fwd_impl(x, w, gamma, beta, eps, interpret)
    return out, mean, var


def _cbr_fwd_impl(x, w, gamma, beta, eps, interpret):
    from jax import lax

    y, s, sq = conv3x3_stats(x, w, interpret=interpret)
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    yf = y.astype(jnp.float32)
    xhat = (yf - mean) * rstd
    out = jnp.maximum(xhat * gamma + beta, 0.0).astype(x.dtype)
    return out, mean, var, (x, w, y, mean, rstd, gamma, out)


def _cbr_fwd(x, w, gamma, beta, eps, interpret):
    out, mean, var, res = _cbr_fwd_impl(x, w, gamma, beta, eps, interpret)
    return (out, mean, var), res


def _cbr_bwd(eps, interpret, res, cts):
    from jax import lax

    x, w, y, mean, rstd, gamma, out = res
    g_out = cts[0].astype(jnp.float32)  # mean/var feed the stop-gradient
    #                                     running-stats update: ct == 0
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mask = out.astype(jnp.float32) > 0
    g = jnp.where(mask, g_out, 0.0)
    xhat = (y.astype(jnp.float32) - mean) * rstd
    dbeta = g.sum(axis=(0, 1, 2))
    dgamma = (g * xhat).sum(axis=(0, 1, 2))
    # standard BN backward (gradient through mean and var):
    dy = (gamma * rstd) * (g - dbeta / n - xhat * (dgamma / n))
    dy = dy.astype(x.dtype)
    wt = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2)  # [3,3,Cout,Cin]
    dx = conv3x3_plain(dy, wt, interpret=interpret)
    # weight grad through XLA's conv machinery (it is a conv over the
    # batch dim; nothing Pallas would improve here)
    _, w_vjp = jax.vjp(
        lambda w_: lax.conv_general_dilated(
            x, w_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ), w,
    )
    (dw,) = w_vjp(dy)
    return dx, dw, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


conv3x3_bn_relu_train.defvjp(_cbr_fwd, _cbr_bwd)


# ---------------------------------------------------------------------------
# XLA reference twins (the A side of the A/B): exactly what the compiler
# builds today for the same math.
# ---------------------------------------------------------------------------
def xla_conv3x3_bn_relu(x, w, scale, bias):
    from jax import lax

    y = lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return jnp.maximum(
        y.astype(jnp.float32) * scale + bias, 0).astype(x.dtype)


def xla_conv3x3_stats(x, w):
    from jax import lax

    y = lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    yf = y.astype(jnp.float32)
    return y, yf.sum(axis=(0, 1, 2)), (yf * yf).sum(axis=(0, 1, 2))
