"""Pallas elementwise kernels for the HBM-bound ResNet joins.

An earlier profile named the 56×56 residual-add fusions (3 × 5.45 ms
at batch 256) as the one untried framework-side lever on the ResNet-50
headline; this module is that experiment's kernel.  ``residual_relu``
computes ``relu(x + y)`` in one HBM pass with explicit [rows, 256]
blocking; ``scripts/pallas_residual_experiment.py`` measures it against
XLA's own elementwise fusion standalone and end-to-end (the result —
a measured negative on an earlier chip path — is recorded in the root
PERF.md).

``scale_bias_relu`` is the compute-tier companion: the
norm+activation join ``relu(x * scale + bias)`` —
the elementwise half of every BatchNorm→ReLU pair once the per-channel
statistics are folded — in one HBM pass with a custom VJP whose
backward reuses the masked-grad kernel.  models/resnet.py wires it in
as ``norm_act="pallas"`` (the ``BatchNormReLU`` module).

Off-TPU the kernels run in Pallas interpreter mode, same policy as
ops/flash_attention.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _resolve_interpret


def _residual_relu_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = jnp.maximum(x_ref[...] + y_ref[...], 0)


def _relu_grad_kernel(o_ref, g_ref, dx_ref):
    # compare in f32: Mosaic can't lower bf16 vector cmpf on this target
    mask = o_ref[...].astype(jnp.float32) > 0
    dx_ref[...] = jnp.where(mask, g_ref[...], jnp.zeros_like(g_ref[...]))


# per-buffer VMEM budget: 3 buffers x 2 (double buffering) must fit the
# ~16 MB scoped-vmem limit with headroom
_BLOCK_BYTES = 2 << 20


def _flat_call(kernel, a, b, *, block_rows, interpret):
    lanes = a.shape[-1]
    af = a.reshape(-1, lanes)
    bf = b.reshape(-1, lanes)
    rows = af.shape[0]
    cap = max(8, _BLOCK_BYTES // (lanes * a.dtype.itemsize))
    block = min(block_rows, cap, rows)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            pl.BlockSpec((block, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), a.dtype),
        interpret=_resolve_interpret(interpret),
    )(af, bf)
    return out.reshape(a.shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def residual_relu(x, y, block_rows: int = 1024,
                  interpret: Optional[bool] = None):
    """``relu(x + y)`` as a single Pallas pass (custom VJP: the backward
    is one masked pass reusing the saved output, the same residual the
    XLA fusion keeps).

    Shapes: any, as long as x and y match; internally flattened to
    [rows, lanes] with the trailing dimension kept whole (channel-last
    NHWC tensors put C on the lanes, which is the TPU-friendly layout).
    """
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch: {x.shape} vs {y.shape}")
    return _flat_call(_residual_relu_kernel, x, y,
                      block_rows=block_rows, interpret=interpret)


def _residual_relu_fwd(x, y, block_rows, interpret):
    out = residual_relu(x, y, block_rows, interpret)
    return out, out


def _residual_relu_bwd(block_rows, interpret, out, g):
    dx = _flat_call(_relu_grad_kernel, out, g,
                    block_rows=block_rows, interpret=interpret)
    return dx, dx


residual_relu.defvjp(_residual_relu_fwd, _residual_relu_bwd)


# ---------------------------------------------------------------------------
# norm+activation join: relu(x * scale + bias) in one pass
# ---------------------------------------------------------------------------
def _scale_bias_relu_kernel(x_ref, s_ref, b_ref, o_ref):
    y = x_ref[...].astype(jnp.float32) * s_ref[0][None, :] + b_ref[0][None, :]
    o_ref[...] = jnp.maximum(y, 0).astype(o_ref.dtype)


def _affine_call(x, scale, bias, *, block_rows, interpret):
    """One blocked pass of the affine+relu kernel; scale/bias ride as
    [1, C] rows broadcast to every block (the conv_bn.py layout)."""
    lanes = x.shape[-1]
    xf = x.reshape(-1, lanes)
    rows = xf.shape[0]
    cap = max(8, _BLOCK_BYTES // (lanes * x.dtype.itemsize))
    block = min(block_rows, cap, rows)
    out = pl.pallas_call(
        _scale_bias_relu_kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            pl.BlockSpec((block, lanes), lambda i: (i, 0)),
            pl.BlockSpec((1, lanes), lambda i: (0, 0)),
            pl.BlockSpec((1, lanes), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        interpret=_resolve_interpret(interpret),
    )(xf, scale.reshape(1, lanes).astype(jnp.float32),
      bias.reshape(1, lanes).astype(jnp.float32))
    return out.reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def scale_bias_relu(x, scale, bias, block_rows: int = 1024,
                    interpret: Optional[bool] = None):
    """``relu(x * scale + bias)`` as a single Pallas pass — the folded
    norm+activation join.  ``x``: any shape with channels last;
    ``scale``/``bias``: [C] (f32 — the folded BN affine).  The custom
    VJP masks the upstream gradient with the saved output (one masked
    pass, the ``residual_relu`` backward kernel) and reduces
    ``dscale``/``dbias`` over the non-channel axes; gradients flow to
    ``scale``/``bias`` so a caller computing them from batch statistics
    gets the full BatchNorm backward through ordinary autodiff
    (models/resnet.py ``BatchNormReLU``)."""
    if scale.shape != (x.shape[-1],) or bias.shape != (x.shape[-1],):
        raise ValueError(
            f"scale/bias must be [{x.shape[-1]}], got "
            f"{scale.shape} / {bias.shape}")
    return _affine_call(x, scale, bias, block_rows=block_rows,
                        interpret=interpret)


def _scale_bias_relu_fwd(x, scale, bias, block_rows, interpret):
    out = scale_bias_relu(x, scale, bias, block_rows, interpret)
    return out, (x, scale, out)


def _scale_bias_relu_bwd(block_rows, interpret, res, g):
    x, scale, out = res
    # masked upstream grad in one pass (reuses the relu-grad kernel)
    gm = _flat_call(_relu_grad_kernel, out, g,
                    block_rows=block_rows, interpret=interpret)
    gm32 = gm.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    dx = (gm32 * scale).astype(x.dtype)
    dscale = (gm32 * x.astype(jnp.float32)).sum(axis=axes)
    dbias = gm32.sum(axis=axes)
    return dx, dscale.astype(scale.dtype), dbias.astype(scale.dtype)


scale_bias_relu.defvjp(_scale_bias_relu_fwd, _scale_bias_relu_bwd)
