"""The diagonal state-space recurrence of Mamba-2 (SSD), chunked.

Per head, with a state ``h`` of ``[P, N]`` (a head's ``P`` channels by the
``N`` state dimensions, ``h_0 = 0``), a step ``dt_t > 0`` and a decay rate
``A < 0`` a head::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t + D x_t

``B_t`` and ``C_t`` (``[N]``) belong to a *group* of heads: group ``g``
serves the heads ``g r .. g r + r - 1`` (``r = heads / groups``), and is read
once for all of them, never repeated in HBM.

:func:`ssd_recurrence` is that recurrence, token by token, in float32: the
definition.  :func:`ssd` computes the same thing ``chunk`` tokens at a time,
so that nearly all of the work is matrix products.

The chunked form.  Write ``a_t = dt_t A`` (``<= 0``) and, inside a chunk
that starts from state ``h_0``, ``G_t`` for the sum of ``a`` up to and
including row ``t``::

    L[t, j]  = exp(G_t - G_j)                       (j <= t, else 0)
    Y        = (L o C B^T) (dt x)  +  exp(G) o (C h_0)
    h_C      = exp(G_C) h_0 + (exp(G_C - G) o dt x)^T B

One state a chunk is carried from chunk to chunk in float32 (a ``lax.scan``
over the chunks: two elementwise ops a step).  Every exponent taken is ``<=
0`` and masked before ``exp``, not after.  The matrix products (``C B^T``,
the masked product with ``dt x``, ``C h_0``, the chunk's state) run through
XLA with operands in ``x``'s dtype (bfloat16 in the models, float32 in the
tests) and accumulate in float32; ``dt``, the decays and ``L`` are float32.

The backward pass.  :func:`ssd` is a ``jax.custom_vjp`` whose forward rule
keeps the call's operands and its output by name (:data:`SSD_IN`,
:data:`SSD_OUT`: ``jax.ad_checkpoint.checkpoint_name``, as
``ops/gated_delta.py`` names its own) and whose backward rule runs the chunk
algebra again from the operands and transposes it: nothing of a chunk's
algebra (``L`` is ``[b, chunks, heads, chunk, chunk]`` float32, 268 MB a
layer at 8192 tokens and 64 heads) lives from the forward pass to the
backward.  A recomputed layer whose policy saves the two names
(``models/recompute.py``) does not run the scan a second time.  This is the
seam a Pallas kernel pair would take (``_scan``'s two rules; PERF.md
section 7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

SCAN_SCOPE = "hvd_ssm_scan"
# What the differentiated call keeps for its backward rule, by
# ``checkpoint_name``: its output, and x, dt, B and C as it takes them
# (``models/recompute.py`` keeps the first whatever the budget and ranks
# the second against it).
SSD_OUT = "hvd_ssm_scan_out"
SSD_IN = "hvd_ssm_scan_in"

_F32 = jnp.float32


def ssd_recurrence(x, dt, A, B, C, D):
    """The recurrence itself, a ``lax.scan`` over tokens in float32.

    Args and result as :func:`ssd`."""
    dtype = x.dtype
    r = _heads_per_group(x, B)
    x, dt, B, C = (jnp.moveaxis(t.astype(_F32), 1, 0) for t in (x, dt, B, C))
    A, D = A.astype(_F32), D.astype(_F32)

    def token(h, row):
        x_t, dt_t, b_t, c_t = row              # [b, h, p], [b, h], [b, g, n]
        b_t, c_t = (jnp.repeat(t, r, axis=1) for t in (b_t, c_t))
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + D[:, None] * x_t

    b, heads, p = x.shape[1:]
    _, y = lax.scan(token, jnp.zeros((b, heads, p, B.shape[-1]), _F32),
                    (x, dt, B, C))
    return jnp.moveaxis(y, 0, 1).astype(dtype)


def _heads_per_group(x, B) -> int:
    """Group ``g`` serves heads ``g * r .. g * r + r - 1``."""
    heads, groups = x.shape[2], B.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads are not whole groups of "
                         f"{groups} B / C groups")
    return heads // groups


def _dot(subscripts, *operands):
    """A product that accumulates in float32; exact for float32 operands
    (a TPU's default there is one bfloat16 pass)."""
    exact = lax.Precision.HIGHEST if operands[0].dtype == _F32 else None
    return jnp.einsum(subscripts, *operands, precision=exact,
                      preferred_element_type=_F32)


def _chunked(chunk, x, dt, A, B, C, D):
    """The chunked form on ``[b, s, h, p]`` / ``[b, s, h]`` / ``[b, s, g,
    n]`` operands; a sequence that is not whole chunks is padded at its end
    with rows of ``dt = 0``, which neither decay the state nor write to it,
    and cut off again."""
    b, seq, heads, p = x.shape
    groups, n = B.shape[2:]
    r, dtype = heads // groups, x.dtype
    c = min(chunk, seq)
    pad = -seq % c
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (seq + pad) // c
    dt = dt.astype(_F32).reshape(b, nc, c, groups, r)
    xs = x.reshape(b, nc, c, groups, r, p)
    B, C = (t.astype(dtype).reshape(b, nc, c, groups, n) for t in (B, C))
    # G_t: the log of the decay from the chunk's start up to and with row t
    cum = jnp.cumsum(dt * A.astype(_F32).reshape(groups, r), axis=2)
    dtx32 = dt[..., None] * xs.astype(_F32)
    dtx = dtx32.astype(dtype)

    # inside a chunk: (L o C B^T) (dt x)
    rows = jnp.arange(c)
    seen = rows[:, None] >= rows[None, :]                       # [t, j]
    gap = cum[:, :, :, None] - cum[:, :, None, :]               # [b,nc,t,j,g,r]
    decay = jnp.exp(jnp.where(seen[:, :, None, None], gap, -jnp.inf))
    scores = _dot("bntgk,bnjgk->bntjg", C, B)
    y = _dot("bntjgr,bnjgrp->bntgrp",
             (scores[..., None] * decay).astype(dtype), dtx)

    # each chunk's own state, from zero, and the decay over the whole chunk
    last = cum[:, :, -1]                                        # [b, nc, g, r]
    to_end = jnp.exp(last[:, :, None] - cum)                    # [b,nc,c,g,r]
    own = _dot("bnjgrp,bnjgk->bngrpk",
               (to_end[..., None] * dtx32).astype(dtype), B)

    # from chunk to chunk: the state each chunk starts from, in float32
    def carry(h, chunk_):
        own_n, last_n = chunk_
        return jnp.exp(last_n)[..., None, None] * h + own_n, h

    _, start = lax.scan(carry, jnp.zeros((b, groups, r, p, n), _F32),
                        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(last, 1, 0)))
    start = jnp.moveaxis(start, 0, 1)                           # [b,nc,g,r,p,k]

    # what the state a chunk starts from gives its rows
    y = y + jnp.exp(cum)[..., None] * _dot(
        "bntgk,bngrpk->bntgrp", C, start.astype(dtype))
    y = y + D.astype(_F32).reshape(groups, r, 1) * xs.astype(_F32)
    return y.reshape(b, nc * c, heads, p)[:, :seq].astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(chunk, x, dt, A, B, C, D):
    return _chunked(chunk, x, dt, A, B, C, D)


def _scan_fwd(chunk, x, dt, A, B, C, D):
    # the operands are named as they arrive: a checkpoint that saves both
    # names has nothing under the scan's scope left to make again
    x, dt, B, C = (checkpoint_name(t, SSD_IN) for t in (x, dt, B, C))
    y = checkpoint_name(_chunked(chunk, x, dt, A, B, C, D), SSD_OUT)
    return y, (x, dt, A, B, C, D)


def _scan_bwd(chunk, operands, dy):
    with jax.named_scope(SCAN_SCOPE):
        return jax.vjp(functools.partial(_chunked, chunk), *operands)[1](dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


def residual_bytes(b: int, s: int, heads: int, head_dim: int,
                   itemsize: int) -> int:
    """Bytes a differentiated call at these sizes keeps for its backward
    rule beyond its operands: the output."""
    return b * s * heads * head_dim * itemsize


def operand_bytes(b: int, s: int, heads: int, head_dim: int, groups: int,
                  state: int, itemsize: int) -> int:
    """Bytes of what :data:`SSD_IN` names: x, B and C in the caller's dtype
    and ``dt`` in float32."""
    return b * s * ((heads * head_dim + 2 * groups * state) * itemsize
                    + heads * 4)


def ssd(x, dt, A, B, C, D, chunk: int = 128):
    """Chunked state-space scan, differentiable in every argument.

    Args:
      x: ``[batch, seq, heads, head_dim]``.
      dt: ``[batch, seq, heads]``, each step's length, ``>= 0`` (after the
        model's softplus); taken in float32.
      A: ``[heads]``, the decay rate, ``< 0`` (``-exp(A_log)``).
      B, C: ``[batch, seq, groups, state]``; ``heads`` is a multiple of
        ``groups`` and group ``g`` serves heads ``g * r .. g * r + r - 1``.
      D: ``[heads]``, the skip from ``x`` to ``y``.
      chunk: tokens a chunk.  A sequence that is not a multiple of it is
        padded at its end with rows that neither decay nor write (``dt =
        0``) and cut off again; one shorter than a chunk is one chunk.

    Returns ``y``: ``[batch, seq, heads, head_dim]`` in ``x``'s dtype.
    """
    if chunk < 1:
        raise ValueError(f"chunk {chunk} is not a positive number of tokens")
    _heads_per_group(x, B)
    if dt.shape != x.shape[:3] or C.shape != B.shape \
            or A.shape != x.shape[2:3] or D.shape != A.shape:
        raise ValueError(
            f"ssd takes x [b, s, h, p], dt [b, s, h], A and D [h], B and C "
            f"[b, s, g, n]; got {x.shape}, {dt.shape}, {A.shape}, "
            f"{B.shape}, {C.shape}, {D.shape}")
    with jax.named_scope(SCAN_SCOPE):
        return _scan(int(chunk), x, dt, A, B, C, D)
