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

One state a chunk is carried from chunk to chunk in float32.  Every
exponent taken is ``<= 0`` and masked before ``exp``, not after.  The matrix
products (``C B^T``, the masked product with ``dt x``, ``C h_0``, the
chunk's state) take operands in ``x``'s dtype (bfloat16 in the models,
float32 in the tests) and accumulate in float32; ``dt``, the running sums,
the decays and ``L`` are float32, and ``(C B^T o L)``, ``dt x`` and ``h_0``
are rounded to ``x``'s dtype where they enter a product.

Two launches of that one algebra, chosen from what the call can observe
(:func:`_path`: the platform and the shapes, never a flag):

* **Pallas kernels** on a TPU where a bundle of heads (``w p`` columns: two
  heads of 64), the state and the chunk are whole lane tiles.  The grid is
  (batch, B / C group, blocks of :data:`CHUNKS_PER_STEP` chunks), the last
  axis sequential: a grid step serves one group, whose heads share ``C
  B^T`` (once a chunk, not once a head) and whose state (``[r p, n]``
  float32, 256 KB for 8 heads of 64 by 128) stays in VMEM scratch from the
  first block to the last.  x and y are read and written as ``[b, s, heads
  p]``, B and C as ``[b, s, groups n]``, by index map: nothing is swapped
  round the call.  ``dt`` and the running sums of ``dt A`` travel as rows
  (``steps``: ``[b, g, chunks, 2 r, chunk]`` float32, 2 MB a layer, made by
  XLA under the scan's scope) and are turned into columns inside the
  kernel.  Nothing of a chunk (``L``, the masked scores, ``dt x``, its own
  state) crosses HBM.  A grid step walks its chunks in a loop; a chunk's
  bundles of heads stand one after another in the program's text, because
  a bundle alone is one chain of waits (``_each_bundle``).
* **XLA ops** (:func:`_chunked`) everywhere else: the CPU path of every
  model test, shapes that do not tile, and the reference the kernels are
  tested against.  ``L`` is ``[b, chunks, heads, chunk, chunk]`` float32
  there (268 MB a layer at 8192 tokens and 64 heads) and a ``lax.scan``
  carries the state.

``hvd_ssm_scan_chunks_traced_total{kernel, path}`` says which one a traced
call took.

The backward pass.  :func:`ssd` is a ``jax.custom_vjp`` on either path
whose forward rule keeps the call's operands and its output by name
(:data:`SSD_IN`, :data:`SSD_OUT`: ``jax.ad_checkpoint.checkpoint_name``, as
``ops/gated_delta.py`` names its own) and nothing else: a recomputed layer
whose policy saves the two names (``models/recompute.py``) does not run the
scan a second time, and no state lives from the forward pass to the
backward.  The kernels' backward rule first makes the state every chunk
starts from again (``hvd_ssm_scan_states``: one product of the forward's
three, 134 MB a layer in float32, a temporary of the backward pass alone),
then one kernel walks the chunks in reverse with the state's gradient in
VMEM, recomputes the chunk's algebra from the operands and writes dx, dB and
dC (summed over a group's heads before they leave VMEM), the gradients of
``steps`` (from which XLA takes those of ``dt`` and ``A``: a reverse running
sum a chunk) and ``D``'s a column.  The XLA form's backward rule is
``jax.vjp`` of :func:`_chunked`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from .flash_attention import _jit_kernel, _loop, _on_tpu
from .gated_delta import (_NN, _NT, _TN, _chunk_rows, _launch, _sum_all,
                          _times_scalar)

SCAN_SCOPE = "hvd_ssm_scan"
# What the differentiated call keeps for its backward rule, by
# ``checkpoint_name``: its output, and x, dt, B and C as it takes them
# (``models/recompute.py`` keeps the first whatever the budget and ranks
# the second against it).
SSD_OUT = "hvd_ssm_scan_out"
SSD_IN = "hvd_ssm_scan_in"
# The three kernels' names: each ``pallas_call``'s ``name=`` and the
# ``jax.named_scope`` it runs under, inside SCAN_SCOPE (docs/profiling.md).
FWD_KERNEL = "hvd_ssm_scan_fwd"
STATES_KERNEL = "hvd_ssm_scan_states"
BWD_KERNEL = "hvd_ssm_scan_bwd"
# Chunks a grid step walks: 4 chunks of 128 are 512 rows a block, 0.5 MB
# of VMEM for x, y or a gradient of theirs and 1 MB for the chunks' states
# (2, 4 and 8 time the same at the benchmark's shape: ``chip_smoke.py
# ssd_sweep``).
CHUNKS_PER_STEP = 4

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


def _named(x, dt, B, C):
    """The operands named as they arrive: a checkpoint that saves both
    names has nothing under the scan's scope left to make again."""
    return tuple(checkpoint_name(t, SSD_IN) for t in (x, dt, B, C))


def _scan_fwd(chunk, x, dt, A, B, C, D):
    x, dt, B, C = _named(x, dt, B, C)
    y = checkpoint_name(_chunked(chunk, x, dt, A, B, C, D), SSD_OUT)
    return y, (x, dt, A, B, C, D)


def _scan_bwd(chunk, operands, dy):
    _count_chunks("bwd", "xla", operands[0].shape, chunk)
    with jax.named_scope(SCAN_SCOPE):
        return jax.vjp(functools.partial(_chunked, chunk), *operands)[1](dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# the same algebra as Pallas kernels
#
# The grid is (batch, B / C group, blocks of CHUNKS_PER_STEP chunks), the
# last axis sequential.  A grid step walks its chunks in a loop, and inside
# a chunk the group's heads a *bundle* at a time (``_each_bundle``): ``w``
# heads whose ``w * p`` columns fill the 128 lanes (two heads of 64), worked
# side by side as the delta-rule kernels work theirs.  ``steps`` holds what the recurrence
# needs of ``dt``, tokens along the lanes: ``[b, g, chunks, 2 r, chunk]``
# float32, a head's ``dt`` in row ``h`` and the running sum of ``dt A``
# from its chunk's start in row ``r + h`` (2 MB a layer; a column is made
# from a row inside the kernel).
# ---------------------------------------------------------------------------


class _Geometry(NamedTuple):
    """The static sizes a kernel body needs beyond its refs' shapes."""
    c: int  # tokens a chunk
    r: int  # heads a group
    p: int  # channels a head
    w: int  # heads a bundle


def _kdot(a, b, dims=_NN):
    """:func:`_dot` on values in VMEM."""
    exact = lax.Precision.HIGHEST if a.dtype == _F32 else None
    return lax.dot_general(a, b, dims, precision=exact,
                           preferred_element_type=_F32)


def _square_iotas(c):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _head_masks(rows, geo):
    """Which of a bundle's ``w * p`` lanes are head ``j``'s, for every
    ``j``: ``[rows, w * p]`` each."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, geo.w * geo.p), 1)
    return [(lane >= j * geo.p) & (lane < (j + 1) * geo.p)
            for j in range(geo.w)]


def _spread(per_head, masks):
    """One ``[c, 1]`` column a head -> ``[c, w * p]``, each head's value
    along its own lanes."""
    wide = jnp.broadcast_to(per_head[0], masks[0].shape)
    for value, mask in zip(per_head[1:], masks[1:]):
        wide = jnp.where(mask, value, wide)
    return wide


def _block_diagonal(wide, masks):
    """``[c, w * p]`` -> ``[w * c, w * p]``: head ``j``'s columns in rows
    ``j c ..``, zeros elsewhere, so that one product with the heads'
    ``[c, c]`` matrices side by side (or stacked) multiplies head by
    head."""
    if len(masks) == 1:
        return wide
    return jnp.concatenate(
        [jnp.where(mask, wide, jnp.zeros_like(wide)) for mask in masks],
        axis=0)


class _Head(NamedTuple):
    dt: jax.Array      # [c, 1]
    g: jax.Array       # [c, 1], G_t: the sum of dt A from the chunk's start
    e_in: jax.Array    # [c, 1], exp(G_t)
    to_end: jax.Array  # [c, 1], exp(G_C - G_t)
    g_row: jax.Array   # [1, c]
    last: jax.Array    # [1, 1], G_C: G at the chunk's last row


def _bundle_heads(steps_ref, i, k, geo, eye, weight=None):
    """Chunk ``i``'s steps for the heads of bundle ``k``.  What the algebra
    wants as columns is worked out along the rows first (a vreg a row, not
    sixteen a column); a column is its row laid on the diagonal and summed
    along the lanes.  With ``weight`` the one column a head, in ``dt``'s
    place, is ``weight(dt, G, G_C)``."""
    def column(row):
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    at_end = lax.broadcasted_iota(jnp.int32, (1, geo.c), 1) == geo.c - 1
    heads = []
    for j in range(geo.w):
        h = k * geo.w + j
        dt_row = steps_ref[0, 0, i, pl.ds(h, 1), :]
        g_row = steps_ref[0, 0, i, pl.ds(geo.r + h, 1), :]
        last = _lane_sum(jnp.where(at_end, g_row, 0.0))
        if weight is None:
            rows = (dt_row, g_row, jnp.exp(g_row), jnp.exp(last - g_row))
        else:
            rows = (weight(dt_row, g_row, last), None, None, None)
        heads.append(_Head(*(None if row is None else column(row)
                             for row in rows), g_row, last))
    return heads


def _decay(head, seen):
    """``L``: ``exp(G_t - G_j)`` where ``j <= t`` and 0 elsewhere; the
    exponent is masked first, so nothing above the diagonal is ever
    exponentiated."""
    return jnp.exp(jnp.where(seen, head.g - head.g_row, -jnp.inf))


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _bundle_slices(k, geo):
    """(the bundle's lanes of a ``[.., r * p]`` block, its first row of the
    ``[r * p, n]`` state)."""
    first = k * geo.w * geo.p
    return pl.ds(first, geo.w * geo.p), first


def _each_bundle(geo, body):
    """``body(k)`` for every bundle of the group, one after another in the
    program's text and not in a loop: the bundles share nothing but the
    chunk's ``C B^T``, and a bundle alone is one long chain (a row, its
    column, a product, the state), so the scheduler needs the four side by
    side to fill the chain's waits (the three kernels over a block of the
    cell's scan, alone on the chip: 3.72 ms as a loop, 2.50 ms this way; the
    kernels compile in a second or two either way)."""
    for k in range(geo.r // geo.w):
        body(k)


def _advance(s_ref, first, h0, left, heads, b_rows, geo):
    """``h_C = exp(G_C) h_0 + left^T B`` for a bundle, ``left`` being
    ``exp(G_C - G) o dt x`` in the products' dtype."""
    own = _kdot(left, b_rows, _TN)
    for j, h in enumerate(heads):
        rows = slice(j * geo.p, (j + 1) * geo.p)
        s_ref[pl.ds(first + j * geo.p, geo.p), :] = (
            _times_scalar(h0[rows], jnp.exp(h.last)) + own[rows])


def _fwd_kernel(x_ref, b_ref, c_ref, steps_ref, skip_ref, y_ref, s_ref, *,
                geo):
    """One block of chunks of one group's heads; ``s_ref`` is their state,
    ``[r * p, n]`` float32, from the first block to the last."""
    c, dtype = geo.c, x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)

    def one_chunk(i):
        rows = _chunk_rows(i, c)
        b_rows, c_rows = b_ref[0, rows, :], c_ref[0, rows, :]
        scores = _kdot(c_rows, b_rows, _NT)
        row, col = _square_iotas(c)
        eye, seen = row == col, row >= col
        masks = _head_masks(c, geo)

        def one_bundle(k):
            lanes, first = _bundle_slices(k, geo)
            heads = _bundle_heads(steps_ref, i, k, geo, eye)
            x32 = x_ref[0, rows, lanes].astype(_F32)
            dtx32 = _spread([h.dt for h in heads], masks) * x32
            inside = jnp.concatenate(
                [(scores * _decay(h, seen)).astype(dtype) for h in heads],
                axis=1)
            y = _kdot(inside, _block_diagonal(dtx32.astype(dtype), masks))
            h0 = s_ref[pl.ds(first, geo.w * geo.p), :]
            y = y + _spread([h.e_in for h in heads], masks) * _kdot(
                c_rows, h0.astype(dtype), _NT)
            y = y + skip_ref[0, :, lanes] * x32
            y_ref[0, rows, lanes] = y.astype(y_ref.dtype)
            to_end = _spread([h.to_end for h in heads], masks)
            _advance(s_ref, first, h0, (to_end * dtx32).astype(dtype), heads,
                     b_rows, geo)

        _each_bundle(geo, one_bundle)

    _loop(0, x_ref.shape[1] // c, one_chunk)


def _states_kernel(x_ref, b_ref, steps_ref, h0_ref, s_ref, *, geo):
    """The forward kernel's walk with nothing but the state: writes the
    state every chunk starts from."""
    c, dtype = geo.c, x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)

    def one_chunk(i):
        rows = _chunk_rows(i, c)
        b_rows = b_ref[0, rows, :]
        row, col = _square_iotas(c)
        masks = _head_masks(c, geo)
        h0_ref[0, 0, i] = s_ref[:]

        def one_bundle(k):
            lanes, first = _bundle_slices(k, geo)
            # ``dt exp(G_C - G)`` is taken along the row and turned into
            # a column once a head
            heads = _bundle_heads(steps_ref, i, k, geo, row == col,
                                  lambda dt, g, last: dt * jnp.exp(last - g))
            left = _spread([h.dt for h in heads], masks) \
                * x_ref[0, rows, lanes].astype(_F32)
            _advance(s_ref, first, s_ref[pl.ds(first, geo.w * geo.p), :],
                     left.astype(dtype), heads, b_rows, geo)

        _each_bundle(geo, one_bundle)

    _loop(0, x_ref.shape[1] // c, one_chunk)


def _bwd_kernel(x_ref, b_ref, c_ref, steps_ref, skip_ref, h0_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dsteps_ref, dskip_ref,
                dh_ref, ds_ref, dbc_ref, *, geo):
    """The forward kernel's block, walked backwards.  ``dh_ref`` carries
    the gradient of the state a chunk leaves; ``ds_ref`` (``[c, c]``) and
    ``dbc_ref`` (``[2, c, n]``) sum a chunk's gradients of ``C B^T``, B and
    C over the group's heads before they leave VMEM.  ``dsteps_ref`` takes
    the gradients of ``steps``, ``dskip_ref`` those of ``D`` a column,
    summed over the whole sequence."""
    c, r, p, w = geo
    dtype = x_ref.dtype
    n_chunks = x_ref.shape[1] // c

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_ref[:] = jnp.zeros_like(dh_ref)
        dskip_ref[:] = jnp.zeros_like(dskip_ref)

    def one_chunk(step):
        i = n_chunks - 1 - step
        rows = _chunk_rows(i, c)
        b_rows, c_rows = b_ref[0, rows, :], c_ref[0, rows, :]
        scores = _kdot(c_rows, b_rows, _NT)
        row, col = _square_iotas(c)
        eye, seen = row == col, row >= col
        masks = _head_masks(c, geo)
        ds_ref[:] = jnp.zeros_like(ds_ref)
        dbc_ref[:] = jnp.zeros_like(dbc_ref)

        def one_bundle(k):
            lanes, first = _bundle_slices(k, geo)
            heads = _bundle_heads(steps_ref, i, k, geo, eye)
            x32 = x_ref[0, rows, lanes].astype(_F32)
            dy = dy_ref[0, rows, lanes]
            dy32 = dy.astype(_F32)
            dt = _spread([h.dt for h in heads], masks)
            dtx32 = dt * x32
            # y = (scores o L)(dt x) + exp(G) o (C h_0) + D x
            decay = [_decay(h, seen) for h in heads]
            inside32 = [scores * d for d in decay]
            inside = jnp.concatenate([m.astype(dtype) for m in inside32],
                                     axis=0)                  # [w c, c]
            dy_heads = _block_diagonal(dy, masks)             # [w c, w p]
            dinside = _kdot(dy_heads, dtx32.astype(dtype), _NT)
            ddtx = _kdot(inside, dy_heads, _TN)               # [c, w p]
            h0 = h0_ref[0, 0, i, pl.ds(first, w * p), :]
            dh = dh_ref[pl.ds(first, w * p), :]
            h0_op, dh_op = h0.astype(dtype), dh.astype(dtype)
            e_in = _spread([h.e_in for h in heads], masks)
            read = _kdot(c_rows, h0_op, _NT)
            dread = (e_in * dy32).astype(dtype)
            # h_C = exp(G_C) h_0 + (exp(G_C - G) o dt x)^T B
            to_end = _spread([h.to_end for h in heads], masks)
            left32 = to_end * dtx32
            dleft = _kdot(b_rows, dh_op, _NT)
            dbc_ref[0] += _kdot(left32.astype(dtype), dh_op)
            dbc_ref[1] += _kdot(dread, h0_op)
            ddtx = ddtx + to_end * dleft
            dx_ref[0, rows, lanes] = (
                dt * ddtx + skip_ref[0, :, lanes] * dy32).astype(dx_ref.dtype)
            dskip_ref[0, 0, :, lanes] += jnp.sum(dy32 * x32, axis=0,
                                                 keepdims=True)
            through_dt = ddtx * x32
            dh0 = _kdot(dread, c_rows, _TN)                   # [w p, n]
            through_end = dleft * left32
            # a head's sums over its lanes as rows: the arrays turned
            # round once, then summed down the sublanes
            g_rows = (dy32 * e_in * read - through_end).T      # [w p, c]
            dt_rows = through_dt.T
            end_sums = jnp.sum(through_end, axis=0, keepdims=True)
            ds = ds_ref[:]
            for j, h in enumerate(heads):
                dinside_j = dinside[j * c:(j + 1) * c]
                through_decay = dinside_j * inside32[j]
                ds = ds + dinside_j * decay[j]
                mine = slice(j * p, (j + 1) * p)
                leaves = jnp.exp(h.last)
                head = k * w + j
                dlast = _lane_sum(jnp.where(masks[j][:1], end_sums, 0.0)) \
                    + leaves * _sum_all(dh[mine] * h0[mine])
                dg = jnp.sum(through_decay.T - through_decay, axis=0,
                             keepdims=True) \
                    + jnp.sum(g_rows[mine], axis=0, keepdims=True)
                ddt = jnp.sum(dt_rows[mine], axis=0, keepdims=True)
                dsteps_ref[0, 0, i, pl.ds(head, 1), :] = ddt
                dsteps_ref[0, 0, i, pl.ds(r + head, 1), :] = (
                    dg + jnp.where(col[:1] == c - 1, dlast, 0.0))
                dh_ref[pl.ds(first + j * p, p), :] = (
                    _times_scalar(dh[mine], leaves) + dh0[mine])
            ds_ref[:] = ds

        _each_bundle(geo, one_bundle)
        ds_op = ds_ref[:].astype(dtype)
        db_ref[0, rows, :] = (dbc_ref[0] + _kdot(ds_op, c_rows, _TN)).astype(
            db_ref.dtype)
        dc_ref[0, rows, :] = (dbc_ref[1] + _kdot(ds_op, b_rows)).astype(
            dc_ref.dtype)

    _loop(0, n_chunks, one_chunk)


def _specs(geo, n, per_step, block):
    """Block shapes and index maps over the grid (batch, group, step):
    ``(x or y, B or C, steps, D a column, states, dD a column)``;
    ``block(i)`` is the block of the sequence grid step ``i`` takes."""
    t, wide = geo.c * per_step, geo.r * geo.p
    return (
        pl.BlockSpec((1, t, wide), lambda b_, g, i: (b_, block(i), g)),
        pl.BlockSpec((1, t, n), lambda b_, g, i: (b_, block(i), g)),
        pl.BlockSpec((1, 1, per_step, 2 * geo.r, geo.c),
                     lambda b_, g, i: (b_, g, block(i), 0, 0)),
        pl.BlockSpec((1, 1, wide), lambda b_, g, i: (g, 0, 0)),
        pl.BlockSpec((1, 1, per_step, wide, n),
                     lambda b_, g, i: (b_, g, block(i), 0, 0)),
        pl.BlockSpec((1, 1, 1, wide), lambda b_, g, i: (b_, g, 0, 0)))


def _grid(bs, steps, per_step):
    """(the grid, the state's size ``n``)."""
    b, groups, n_chunks = steps.shape[:3]
    return (b, groups, n_chunks // per_step), bs.shape[2] // groups


@_jit_kernel
def _fwd_call(xs, bs, cs, steps, skip, *, geo, per_step, interpret):
    """``xs``: ``[b, seq, h * p]``; ``bs``, ``cs``: ``[b, seq, g * n]``;
    ``steps``: ``[b, g, chunks, 2 r, chunk]`` float32; ``skip``: ``[g, 1, r
    * p]`` float32, ``D`` a column.  Returns y as ``xs``."""
    grid, n = _grid(bs, steps, per_step)
    wide, group, step, column, _, _ = _specs(geo, n, per_step, lambda i: i)
    return _launch(
        FWD_KERNEL, functools.partial(_fwd_kernel, geo=geo), grid,
        [wide, group, group, step, column], (xs, bs, cs, steps, skip),
        wide, jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        scratch=[(geo.r * geo.p, n)], interpret=interpret)


@_jit_kernel
def _states_call(xs, bs, steps, *, geo, per_step, interpret):
    """The state every chunk starts from: ``[b, g, chunks, r * p, n]``
    float32."""
    grid, n = _grid(bs, steps, per_step)
    wide, group, step, _, states, _ = _specs(geo, n, per_step, lambda i: i)
    return _launch(
        STATES_KERNEL, functools.partial(_states_kernel, geo=geo), grid,
        [wide, group, step], (xs, bs, steps), states,
        jax.ShapeDtypeStruct((*steps.shape[:3], geo.r * geo.p, n), _F32),
        scratch=[(geo.r * geo.p, n)], interpret=interpret)


@_jit_kernel
def _bwd_call(xs, bs, cs, steps, skip, states, dys, *, geo, per_step,
              interpret):
    """The gradients of ``xs``, ``bs``, ``cs`` and ``steps`` in their
    shapes, and of ``skip`` a batch row (``[b, g, 1, r * p]``)."""
    grid, n = _grid(bs, steps, per_step)
    wide, group, step, column, state, dcolumn = _specs(
        geo, n, per_step, lambda i: grid[2] - 1 - i)
    return _launch(
        BWD_KERNEL, functools.partial(_bwd_kernel, geo=geo), grid,
        [wide, group, group, step, column, state, wide],
        (xs, bs, cs, steps, skip, states, dys),
        [wide, group, group, step, dcolumn],
        [*(jax.ShapeDtypeStruct(t.shape, t.dtype)
           for t in (xs, bs, cs, steps)),
         jax.ShapeDtypeStruct((grid[0], grid[1], 1, geo.r * geo.p), _F32)],
        scratch=[(geo.r * geo.p, n), (geo.c, geo.c), (2, geo.c, n)],
        interpret=interpret)


class _Layout:
    """The call's operands as the kernels take them (x as ``[b, seq, heads
    p]``, B and C as ``[b, seq, groups n]``: the model's own arrays, so the
    reshapes are free), padded to whole grid steps, and their gradients
    back; ``steps`` is 2 MB a layer."""

    def __init__(self, xs, bs, heads, groups, chunk):
        self.b, self.seq, _ = xs.shape
        self.heads, self.groups = heads, groups
        r, p = heads // groups, xs.shape[2] // heads
        self.geo = _Geometry(chunk, r, p, _heads_per_bundle(r, p))
        chunks = -(-self.seq // chunk)
        self.per_step = min(CHUNKS_PER_STEP, chunks)
        self.chunks = -(-chunks // self.per_step) * self.per_step
        self.pad = self.chunks * chunk - self.seq

    def rows(self, t, dtype):
        """``[b, seq, columns]`` -> ``[b, chunks * chunk, columns]``; the
        rows added neither decay nor write (``dt = 0``)."""
        t = t.astype(dtype)
        return jnp.pad(t, ((0, 0), (0, self.pad), (0, 0))) if self.pad else t

    def steps(self, dt, A):
        """``[b, g, chunks, 2 r, chunk]``: ``dt`` and, under it, the sum of
        ``dt A`` from each chunk's start up to and with the row."""
        c, r, _, _ = self.geo
        dt = jnp.swapaxes(self.rows(dt, _F32), 1, 2).reshape(
            self.b, self.groups, r, self.chunks, c).transpose(0, 1, 3, 2, 4)
        return jnp.concatenate(
            [dt, self.running_sum(dt * self.by_head(A))], axis=3)

    def running_sum(self, rows, reverse=False):
        """The sum along a chunk's tokens up to and with each (from each
        on with ``reverse``), as a product with a triangle of ones in
        float32 arithmetic: XLA's ``cumsum`` over 128 lanes is a
        ``reduce-window`` that takes 0.2-0.8 ms a call on 2 MB."""
        c = self.geo.c
        upto = jnp.tri(c, dtype=_F32) if reverse else jnp.tri(c, dtype=_F32).T
        return jnp.einsum("...j,jt->...t", rows, upto,
                          precision=lax.Precision.HIGHEST)

    def by_head(self, per_head):
        return per_head.astype(_F32).reshape(self.groups, 1, self.geo.r, 1)

    def tokens(self, rows, like):
        """``[b, chunks * chunk, columns]`` -> ``like``'s rows and dtype."""
        return rows[:, :self.seq].astype(like.dtype)

    def operands(self, xs, dt, A, bs, cs, D):
        skip = jnp.repeat(D.astype(_F32), self.geo.p).reshape(
            self.groups, 1, -1)
        return (self.rows(xs, xs.dtype), self.rows(bs, xs.dtype),
                self.rows(cs, xs.dtype), self.steps(dt, A), skip)


def _heads_per_bundle(r, p) -> int:
    """Heads whose columns fill the 128 lanes together, as far as the
    group has them."""
    return math.gcd(r, max(1, 128 // p))


@functools.lru_cache(maxsize=None)
def _kernel_scan(chunk, heads, groups, interpret):
    """The scan through the kernels on ``xs`` ``[b, seq, heads p]``, ``dt``,
    ``A``, ``bs`` and ``cs`` ``[b, seq, groups n]`` and ``D``."""
    path = "interpret" if interpret else "mosaic"

    def count(kernel, xs):
        _count_chunks(kernel, path, (*xs.shape[:2], heads), chunk)

    def forward(xs, dt, A, bs, cs, D):
        count("fwd", xs)
        lay = _Layout(xs, bs, heads, groups, chunk)
        y = _fwd_call(*lay.operands(xs, dt, A, bs, cs, D), geo=lay.geo,
                      per_step=lay.per_step, interpret=interpret)
        return lay.tokens(y, xs)

    @jax.custom_vjp
    def f(xs, dt, A, bs, cs, D):
        return forward(xs, dt, A, bs, cs, D)

    def fwd(xs, dt, A, bs, cs, D):
        # named as the kernels hold them: what a checkpoint saves under
        # either name is rows of whole lane tiles (``[b, seq, heads, 64]``
        # would be laid out anew, a copy of 67 MB, to be kept)
        xs, dt, bs, cs = _named(xs, dt, bs, cs)
        y = checkpoint_name(forward(xs, dt, A, bs, cs, D), SSD_OUT)
        return y, (xs, dt, A, bs, cs, D)

    def bwd(operands, dy):
        xs, dt, A, bs, cs, D = operands
        lay = _Layout(xs, bs, heads, groups, chunk)
        kw = dict(geo=lay.geo, per_step=lay.per_step, interpret=interpret)
        with jax.named_scope(SCAN_SCOPE):
            xr, br, cr, steps, skip = lay.operands(*operands)
            count("states", xs)
            states = _states_call(xr, br, steps, **kw)
            count("bwd", xs)
            dxr, dbr, dcr, dsteps, dskip = _bwd_call(
                xr, br, cr, steps, skip, states, lay.rows(dy, xs.dtype), **kw)
            # G_t sums dt A up to t, so row t takes the gradients of G_t
            # and of all after it in its chunk
            r = lay.geo.r
            da = lay.running_sum(dsteps[:, :, :, r:], reverse=True)
            ddt = dsteps[:, :, :, :r] + da * lay.by_head(A)
            ddt = jnp.swapaxes(ddt.transpose(0, 1, 3, 2, 4).reshape(
                lay.b, heads, -1), 1, 2)
            dA = jnp.sum(da * steps[:, :, :, :r], axis=(0, 2, 4))
            dD = jnp.sum(dskip.reshape(lay.b, heads, -1), axis=(0, 2))
            return (lay.tokens(dxr, xs), lay.tokens(ddt, dt),
                    dA.reshape(A.shape).astype(A.dtype), lay.tokens(dbr, bs),
                    lay.tokens(dcr, cs), dD.astype(D.dtype))

    f.defvjp(fwd, bwd)
    return f


def _count_chunks(kernel, path, shape, chunk):
    """The trace-time counter: once for every traced call of a kernel (or
    of the XLA form's rule), the chunks it walks, of every head; ``shape``
    starts ``(batch, seq, heads)``."""
    from .. import metrics

    b, seq, heads = shape[:3]
    metrics.record_ssm_scan_chunks(kernel, path,
                                   b * heads * -(-seq // chunk))


def _tiles(x, B, chunk) -> bool:
    """What Mosaic's tiling asks: a bundle of heads and the state are whole
    lane tiles of ``[b, seq, columns]``, and so is the chunk, which is the
    width of ``L`` and of ``steps``' rows."""
    r, p = _heads_per_group(x, B), x.shape[3]
    return not ((_heads_per_bundle(r, p) * p) % 128 or B.shape[3] % 128
                or chunk % 128)


def _path(x, B, chunk, interpret) -> str:
    """``xla``, ``mosaic`` or ``interpret``: read from the platform and the
    shapes; ``interpret`` is the tests' argument."""
    if interpret:
        return "interpret"
    if interpret is None and not (_on_tpu() and _tiles(x, B, chunk)):
        return "xla"
    if not _tiles(x, B, chunk):
        raise ValueError(
            f"ssd's kernels take heads whose columns fill lane tiles "
            f"({x.shape[3]} a head, {_heads_per_group(x, B)} a group), a "
            f"state and a chunk that are multiples of 128; got state "
            f"{B.shape[3]}, chunk {chunk}")
    return "mosaic"

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


def ssd(x, dt, A, B, C, D, chunk: int = 128,
        interpret: Optional[bool] = None):
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
    path = _path(x, B, chunk, interpret)
    with jax.named_scope(SCAN_SCOPE):
        if path == "xla":
            _count_chunks("fwd", path, x.shape, chunk)
            return _scan(int(chunk), x, dt, A, B, C, D)
        b, seq, heads, _ = x.shape
        scan = _kernel_scan(int(chunk), heads, B.shape[2],
                            path == "interpret")
        return scan(x.reshape(b, seq, -1), dt, A, B.reshape(b, seq, -1),
                    C.reshape(b, seq, -1), D).reshape(x.shape)
