"""The gated delta rule (Gated DeltaNet linear attention) as Pallas kernels.

Per value head, with a state ``S`` of keys by values (``[dk, dv]``,
``S_0 = 0``), a log decay ``g_t <= 0`` and a write strength ``beta_t``::

    S  <- exp(g_t) * S
    u  =  beta_t * (v_t - S^T k_t)
    S  <- S + k_t u^T
    o_t = S^T q_t

:func:`gated_delta_recurrence` is that recurrence, token by token, in
float32: the definition the kernels are tested against.
:func:`gated_delta_rule` computes the same thing in chunks of ``chunk``
tokens so that nearly all of the work is matrix products.

The chunked form.  Inside a chunk that starts from state ``S_0`` write
``G_t`` for the sum of ``g`` up to and including row ``t``.  The ``u`` of
the chunk's rows solve a unit lower-triangular system (the WY / UT
transform)::

    A[t, j]  = -beta_t * exp(G_t - G_j) * (k_t . k_j)     (j < t, else 0)
    T        = (I - A)^-1
    U        = T (beta * (V - (exp(G) * K) S_0))
    O        = (exp(G) * Q) S_0 + P U    P[t, j] = exp(G_t - G_j) (q_t . k_j), j <= t
    S_C      = exp(G_C) S_0 + (exp(G_C - G) * K)^T U

The kernels.  The grid is (batch, key heads, blocks of
``CHUNKS_PER_STEP`` chunks), the last axis sequential: a grid step walks
its chunks in a loop and the state of each value head the key head serves
stays in VMEM scratch from the first block to the last (forward), the
state's gradient from the last to the first (backward).  Everything of the
chunk algebra above lives in VMEM: nothing but q, k, v, g, beta, o (and
their gradients) crosses HBM, read by index map from ``[b, seq, heads *
d]`` as the model holds them, q and k once for all the value heads they
serve.  ``g`` and ``beta`` travel as rows (``[b, hk, n, r * chunk]``, a
chunk of a key head's ``r`` value heads side by side, 1 MB a layer) and are
turned into columns inside the kernel.  The value heads of a key head are
worked side by side too: two ``[64, 64]`` matrices fill the 128 lanes of a
register and the width of the MXU, so the chunk algebra of two heads costs
what one head's would.

The solve.  Mosaic has no ``triangular_solve`` and row-by-row substitution
is ``chunk`` dependent steps, so ``T`` is the finite product ``(I + A)(I +
A^2)(I + A^4)...``, exact in exact arithmetic because ``A`` is strictly
lower triangular (``A^chunk = 0``): ``log2(chunk) - 1`` levels of two
float32 products, one that squares ``A^(2^k)`` and one that applies the
square to the running ``T``.  On a v5e (PR 29, PERF.md section 6) the ten
products are 0.73 us of the forward kernel's 1.07 us a chunk-head; inverting
diagonal blocks of 16 and taking the rest by products read 6% slower, and
both forms the same to the last bfloat16 digit of every output.

Precision.  The decays are float32 and in log space: every exponent that is
taken is ``<= 0`` (masked before ``exp``, not after).  ``S`` and its
gradient are carried in float32; ``T`` and its products with the right-hand
side are float32 (``Precision.HIGHEST``).  The operands of the other matrix
products have the dtype of ``q`` (bfloat16 in the models, float32 in the
tests) and accumulate in float32.

The backward pass is a kernel too (``jax.custom_vjp``).  The differentiated
forward call writes the state each chunk starts from (``[b, hv, n, dk,
dv]`` float32, 268 MB a layer at 8192 tokens, 32 heads, 128 x 128) and each
chunk's ``T`` (``[b, hk, n, chunk, r * chunk]`` float32, 67 MB a layer:
the solve is half of what a backward kernel that recomputed it would
cost), and nothing else of the chunk algebra; the backward kernel walks the
blocks in reverse, recomputes the rest of each chunk's algebra from the
inputs, and sums dq and dk over the value heads a key head serves before
they leave VMEM.  The plain call writes neither.

On the CPU mesh of the tests, and only there, the kernels run in Pallas
interpreter mode (``ops/flash_attention._resolve_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_count_residuals, _jit_kernel, _loop,
                              _resolve_interpret)

SCAN_SCOPE = "hvd_gdn_scan"
# The two kernels' names: each ``pallas_call``'s ``name=`` and the
# ``jax.named_scope`` it runs under, inside SCAN_SCOPE (docs/profiling.md;
# benchmarks/layer_metrics/gdn_scan_*.py read everything under SCAN_SCOPE).
FWD_KERNEL = "hvd_gdn_scan_fwd"
BWD_KERNEL = "hvd_gdn_scan_bwd"
# What the differentiated forward hands the backward kernel beyond its own
# inputs, by ``checkpoint_name`` (as ``flash_attention.FLASH_OUT`` /
# ``FLASH_LSE``): the output, the chunks' start states and their inverses.
GDN_OUT = "hvd_gdn_scan_out"
GDN_STATES = "hvd_gdn_scan_states"
GDN_INVERSES = "hvd_gdn_scan_inverses"
# The backward kernel's other residuals: q, k, v, g and beta as the kernels
# take them (``models/recompute.py`` ranks the name against its budget).
GDN_IN = "hvd_gdn_scan_in"
# Chunks a grid step walks: one chunk a step would be 36 864 grid steps a
# training step of the benchmark's cell; 8 chunks of 64 are 512 rows a
# block, 0.5 / 1 MB of VMEM an operand.
CHUNKS_PER_STEP = 8

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def gated_delta_recurrence(q, k, v, g, beta):
    """The recurrence itself, a ``lax.scan`` over tokens in float32.

    Args and result as :func:`gated_delta_rule`."""
    r = _value_heads_per_key_head(k, v)
    if r > 1:
        q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    q, k, v, g, beta = (jnp.moveaxis(x.astype(_F32), 1, 0)
                        for x in (q, k, v, g, beta))

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row                      # [b, h, d], [b, h]
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    b, h, dv = v.shape[1:]
    s0 = jnp.zeros((b, h, k.shape[-1], dv), _F32)
    _, o = lax.scan(token, s0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)


def _value_heads_per_key_head(k, v) -> int:
    """Key head ``i`` serves value heads ``i * r .. i * r + r - 1``."""
    key_heads, value_heads = k.shape[2], v.shape[2]
    if value_heads % key_heads:
        raise ValueError(f"{value_heads} value heads are not a multiple of "
                         f"{key_heads} key heads")
    return value_heads // key_heads


# ---------------------------------------------------------------------------
# the chunk algebra, on values in VMEM
#
# A key head's ``r`` value heads are worked side by side: a "wide" array is
# ``[c, r * c]`` with head ``h``'s ``[c, c]`` matrix in lanes ``h * c .. h *
# c + c - 1`` (two heads of 64 fill the 128 lanes and the MXU's width), a
# list holds one ``[c, 1]`` column or ``[c, d]`` block a head.
# ---------------------------------------------------------------------------


def _dot(a, b, dims=_NN):
    """Operands as they are (q's dtype), float32 accumulation."""
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot32(a, b, dims=_NN):
    """A float32 product in float32 arithmetic."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _wide_iotas(c, r, rows=None):
    """``(row, column within its head's block, column)`` of a ``[rows, r *
    c]`` array (``rows`` defaults to ``c``)."""
    shape = (c if rows is None else rows, r * c)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    local = col
    for h in range(1, r):
        local = jnp.where(col >= h * c, col - h * c, local)
    return row, local, col


def _spread(per_head, c):
    """A list of ``[c, 1]`` (or ``[1, 1]``) values a head -> wide."""
    r = len(per_head)
    _, _, col = _wide_iotas(c, r, rows=per_head[0].shape[0])
    wide = jnp.broadcast_to(per_head[0], col.shape)
    for h in range(1, r):
        wide = jnp.where(col >= h * c, per_head[h], wide)
    return wide


def _columns(row, c):
    """``[1, r * c]`` -> a ``[c, 1]`` column a head: the row laid on each
    block's diagonal and summed along the lanes."""
    r = row.shape[1] // c
    rows, _, col = _wide_iotas(c, r)
    return [jnp.sum(jnp.where(col == rows + h * c, row, 0.0), axis=1,
                    keepdims=True) for h in range(r)]


def _as_row(per_head, c):
    """A ``[c, 1]`` column a head -> ``[1, r * c]``."""
    rows, local, _ = _wide_iotas(c, len(per_head))
    return jnp.sum(jnp.where(rows == local, _spread(per_head, c), 0.0),
                   axis=0, keepdims=True)


def _head_sums(wide, c):
    """Row sums of each head's block of a wide array: ``[c, 1]`` a head."""
    _, local, col = _wide_iotas(c, wide.shape[1] // c)
    return [jnp.sum(jnp.where(col - local == h * c, wide, 0.0), axis=1,
                    keepdims=True) for h in range(wide.shape[1] // c)]


def _block_diagonal(blocks):
    """``r`` blocks ``[m, n]`` -> ``[r * m, r * n]`` with them on the
    diagonal."""
    r = len(blocks)
    if r == 1:
        return blocks[0]
    zero = jnp.zeros_like(blocks[0])
    return jnp.concatenate(
        [jnp.concatenate([b if j == h else zero for j in range(r)], axis=1)
         for h, b in enumerate(blocks)], axis=0)


def _diagonal_of(wide, c):
    """Wide ``[c, r * c]`` -> ``[r * c, r * c]``, each head's matrix on the
    diagonal: ``x_wide @ _diagonal_of(y_wide, c)`` multiplies head by
    head."""
    r = wide.shape[1] // c
    if r == 1:
        return wide
    row, local, col = _wide_iotas(c, r, rows=r * c)
    within = row - (col - local)      # the row inside the column's block
    return jnp.where((within >= 0) & (within < c),
                     jnp.concatenate([wide] * r, axis=0), 0.0)


def _diagonal_blocks(tall, c, d):
    """``[r * c, r * d]`` -> its ``r`` diagonal ``[c, d]`` blocks."""
    r = tall.shape[0] // c
    return [tall[h * c:(h + 1) * c, h * d:(h + 1) * d] for h in range(r)]


def _times_scalar(x, scalar):
    """``x`` times a ``[1, 1]`` value, spread along the lanes and then down
    the sublanes (Mosaic has no broadcast in both at once)."""
    return x * jnp.broadcast_to(scalar, (1, x.shape[1]))


def _sum_all(x):
    """``[1, 1]``."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _unit_lower_inverses(low, c):
    """``(I + low_h)^-1`` for the strictly lower triangular ``[c, c]``
    float32 blocks of a wide ``low``: with ``A = -low_h`` the finite product
    ``(I + A)(I + A^2)(I + A^4)...`` (``A^c = 0``).  A level squares
    ``A^(2^k)`` and applies the square to the running inverse, for all the
    heads at once (the right-hand operand has their matrices on its
    diagonal)."""
    rows, local, _ = _wide_iotas(c, low.shape[1] // c)
    power = -low
    inverse = jnp.where(rows == local, 1.0, power)
    by = _diagonal_of(power, c)
    reached = 1                   # ``power`` is A^reached
    while 2 * reached < c:        # A^(2 reached) is not zero yet
        power = _dot32(power, by)
        by = _diagonal_of(power, c)
        inverse = inverse + _dot32(inverse, by)
        reached *= 2
    return inverse


class _Chunk:
    """What one chunk of a key head's ``r`` value heads needs that does not
    depend on the states it starts from.  ``q``, ``k``: ``[c, dk]``;
    ``gamma_row``, ``beta_row``: ``[1, r * c]`` float32, the decay summed
    from the chunk's start and the write strength, head beside head."""

    def __init__(self, q, k, gamma_row, beta_row, c, inverse=None):
        dtype = q.dtype
        r = gamma_row.shape[1] // c
        rows, local, col = _wide_iotas(c, r)
        self.r = r
        self.k_tall = jnp.concatenate([k] * r, axis=0)         # [r c, dk]
        kk = _dot(k, self.k_tall, _NT)                         # wide
        qk = _dot(q, self.k_tall, _NT)
        gamma = _columns(gamma_row, c)
        self.beta = _columns(beta_row, c)
        # exp(G_t - G_j) where j <= t and 0 elsewhere; the exponent is
        # masked first, so nothing above the diagonal is ever exponentiated
        self.decay = jnp.exp(jnp.where(
            rows >= local, _spread(gamma, c) - gamma_row, -jnp.inf))
        # D * k k^T below the diagonal; beta times it is -A
        self.dkk = jnp.where(rows > local, self.decay * kk, 0.0)
        self.low = _spread(self.beta, c) * self.dkk
        self.inverse = (_unit_lower_inverses(self.low, c)       # T, wide
                        if inverse is None else inverse)
        self.p32 = self.decay * qk
        self.p = self.p32.astype(dtype)
        self.total = [jnp.sum(jnp.where(col[:1] == h * c + c - 1, gamma_row,
                                        0.0), axis=1, keepdims=True)
                      for h in range(r)]                       # [1, 1]
        self.e_in = [jnp.exp(g) for g in gamma]                # [c, 1]
        self.e_out = [jnp.exp(t - g) for t, g in zip(self.total, gamma)]
        q32, k32 = q.astype(_F32), k.astype(_F32)
        self.qg32 = [q32 * e for e in self.e_in]
        self.kg32 = [k32 * e for e in self.e_in]
        self.kd32 = [k32 * e for e in self.e_out]
        self.qg, self.kg, self.kd = ([x.astype(dtype) for x in xs] for xs in (
            self.qg32, self.kg32, self.kd32))

    def writes(self, z):
        """The rows ``U`` the chunk writes, ``[c, r * dv]`` float32, from
        ``z_h = v_h - (exp(G) K) S_h``."""
        return _dot32(self.inverse, _block_diagonal(
            [b * z_h for b, z_h in zip(self.beta, z)]))

    def lanes(self, wide, d):
        """The heads' ``[c, d]`` blocks of a ``[c, r * d]`` array."""
        return [wide[:, h * d:(h + 1) * d] for h in range(self.r)]


def _cumulate(g_ref, gamma_ref, c):
    """The decays of every chunk of the block summed from the chunk's
    start, in one product: ``gamma_ref`` is ``[chunks, r * c]``."""
    rows, local, _ = _wide_iotas(c, g_ref.shape[3] // c)
    upto = jnp.where(rows <= local, 1.0, 0.0)
    gamma_ref[:] = _dot32(g_ref[0, 0], _diagonal_of(upto, c))


def _chunk_rows(i, c):
    return pl.ds(pl.multiple_of(i * c, c), c)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                chunk, keep):
    """One block of chunks of one key head and the value heads it serves.
    ``rest``: with ``keep`` the outputs the backward kernel reads (each
    chunk's start states and its inverses ``T``), then scratch: the states
    ``[r, dk, dv]`` and the cumulated decays ``[chunks, r * chunk]``."""
    s0_ref, t_ref = rest[:2] if keep else (None, None)
    s_ref, gamma_ref = rest[-2:]
    c, (r, _, dv) = chunk, s_ref.shape
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)

    _cumulate(g_ref, gamma_ref, c)

    def one_chunk(i):
        rows = _chunk_rows(i, c)
        ch = _Chunk(q_ref[0, rows, :], k_ref[0, rows, :],
                    gamma_ref[pl.ds(i, 1), :],
                    beta_ref[0, 0, pl.ds(i, 1), :], c)
        if keep:
            t_ref[0, 0, i] = ch.inverse
        s, z, read = [], [], []
        for h, v in enumerate(ch.lanes(v_ref[0, rows, :], dv)):
            s.append(s_ref[h])
            if keep:
                s0_ref[0, h, i] = s[h]
            # (exp(G) K) S and (exp(G) Q) S in one product
            both = _dot(jnp.concatenate([ch.kg[h], ch.qg[h]], axis=0),
                        s[h].astype(dtype))
            z.append(v.astype(_F32) - both[:c])
            read.append(both[c:])
        u = ch.lanes(ch.writes(z).astype(dtype), dv)
        o = ch.lanes(_dot(ch.p, _block_diagonal(u)), dv)
        for h in range(r):
            o_ref[0, rows, h * dv:(h + 1) * dv] = (
                read[h] + o[h]).astype(o_ref.dtype)
            s_ref[h] = (_times_scalar(s[h], jnp.exp(ch.total[h]))
                        + _dot(ch.kd[h], u[h], _TN))

    _loop(0, q_ref.shape[1] // c, one_chunk)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_ref, gamma_ref, dgamma_ref, *, chunk):
    """The forward kernel's block, walked backwards: ``ds_ref`` carries
    the gradient of the state a chunk leaves, ``dgamma_ref`` collects the
    gradients of the cumulated decays, which leave as gradients of ``g``
    once the block is done."""
    c, (r, _, dv) = chunk, ds_ref.shape
    dtype = q_ref.dtype
    n = q_ref.shape[1] // c
    rows_w, local, _ = _wide_iotas(c, r)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    _cumulate(g_ref, gamma_ref, c)

    def one_chunk(step):
        i = n - 1 - step
        rows = _chunk_rows(i, c)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        ch = _Chunk(q, k, gamma_ref[pl.ds(i, 1), :],
                    beta_ref[0, 0, pl.ds(i, 1), :], c, t_ref[0, 0, i])
        s = [s0_ref[0, h, i] for h in range(r)]
        s_op = [x.astype(dtype) for x in s]
        z = [v.astype(_F32) - _dot(ch.kg[h], s_op[h]) for h, v in
             enumerate(ch.lanes(v_ref[0, rows, :], dv))]
        u = ch.lanes(ch.writes(z).astype(dtype), dv)
        u_diagonal = _block_diagonal(u)
        ds = [ds_ref[h] for h in range(r)]
        ds_op = [x.astype(dtype) for x in ds]
        do_wide = do_ref[0, rows, :]
        do = ch.lanes(do_wide, dv)
        decay_all = [jnp.exp(t) for t in ch.total]

        # o = qg s + p u;  s' = exp(G_C) s + kd^T u
        du = [x + _dot(ch.kd[h], ds_op[h]) for h, x in enumerate(
            _diagonal_blocks(_dot(ch.p, do_wide, _TN), c, dv))]
        dp = jnp.where(rows_w >= local, _dot(do_wide, u_diagonal, _NT), 0.0)
        dkd = [_dot(u[h], ds_op[h], _NT) for h in range(r)]
        # u = T (beta z), T = (I + low)^-1
        dr = _diagonal_blocks(_dot32(
            ch.inverse, jnp.concatenate(du, axis=1), _TN), c, dv)
        dlow = jnp.where(rows_w > local, -_dot(
            jnp.concatenate(dr, axis=1).astype(dtype), u_diagonal, _NT), 0.0)
        dz = [b * x for b, x in zip(ch.beta, dr)]
        dqg, dkg = [], []
        for h in range(r):
            dv_ref[0, rows, h * dv:(h + 1) * dv] = dz[h].astype(dv_ref.dtype)
            dz_op = dz[h].astype(dtype)
            # do s^T and dz s^T in one product
            both = _dot(jnp.concatenate([do[h], dz_op], axis=0), s_op[h],
                        _NT)
            dqg.append(both[:c])
            dkg.append(-both[c:])
            ds_ref[h] = _times_scalar(ds[h], decay_all[h]) + _dot(
                jnp.concatenate([ch.qg[h], ch.kg[h]], axis=0),
                jnp.concatenate([do[h], -dz_op], axis=0), _TN)
        dbeta = [jnp.sum(x * z_h, axis=1, keepdims=True) + y for x, z_h, y
                 in zip(dr, z, _head_sums(dlow * ch.dkk, c))]
        dbeta_ref[0, 0, pl.ds(i, 1), :] = _as_row(dbeta, c)
        # low = beta D kk, p = D qk, D[t, j] = exp(G_t - G_j); q and k take
        # the sums over the heads
        dkk = (_spread(ch.beta, c) * dlow * ch.decay).astype(dtype)
        dqk = (dp * ch.decay).astype(dtype)
        plain = _dot(jnp.concatenate([dqk, dkk], axis=0), ch.k_tall)
        turned = _dot(jnp.concatenate([dkk, dqk], axis=0),
                      jnp.concatenate([k, q], axis=0), _TN)
        dq = plain[:c] + sum(e * x for e, x in zip(ch.e_in, dqg))
        dk = plain[c:] + sum(turned[h * c:(h + 1) * c] for h in range(r)) \
            + sum(e * x for e, x in zip(ch.e_in, dkg)) \
            + sum(e * x for e, x in zip(ch.e_out, dkd))
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        through_decay = dlow * ch.low + dp * ch.p32
        out = [x * y for x, y in zip(dkd, ch.kd32)]
        dgamma = [a + jnp.sum(dqg[h] * ch.qg32[h] + dkg[h] * ch.kg32[h]
                              - out[h], axis=1, keepdims=True)
                  for h, a in enumerate(_head_sums(through_decay, c))]
        dtotal = [_sum_all(out[h]) + decay_all[h] * _sum_all(ds[h] * s[h])
                  for h in range(r)]
        dgamma_ref[pl.ds(i, 1), :] = (
            _as_row(dgamma, c)
            - jnp.sum(through_decay, axis=0, keepdims=True)
            + jnp.where(local[:1] == c - 1, _spread(dtotal, c), 0.0))

    _loop(0, n, one_chunk)

    # G_t sums g up to t, so g_t takes the gradients of G_t and all after it
    since = _diagonal_of(jnp.where(rows_w >= local, 1.0, 0.0), c)
    dg_ref[0, 0] = _dot32(dgamma_ref[:], since)


# ---------------------------------------------------------------------------
# launchers
# ---------------------------------------------------------------------------


def _specs(r, dk, dv, chunk, per_step, block):
    """Block shapes and index maps over the grid (batch, key head, step):
    ``(q or k, v or o, g or beta, states, inverses)``; ``block(i)`` is the
    block of the sequence grid step ``i`` takes."""
    t = chunk * per_step
    return (
        pl.BlockSpec((1, t, dk), lambda b_, h, i: (b_, block(i), h)),
        pl.BlockSpec((1, t, r * dv), lambda b_, h, i: (b_, block(i), h)),
        pl.BlockSpec((1, 1, per_step, r * chunk),
                     lambda b_, h, i: (b_, h, block(i), 0)),
        pl.BlockSpec((1, r, per_step, dk, dv),
                     lambda b_, h, i: (b_, h, block(i), 0, 0)),
        pl.BlockSpec((1, 1, per_step, chunk, r * chunk),
                     lambda b_, h, i: (b_, h, block(i), 0, 0)))


def _launch(name, kernel, grid, in_specs, ins, out_specs, out_shapes,
            scratch, interpret):
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=_DIM_SEMANTICS)
    call = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in scratch],
        compiler_params=params, interpret=interpret, name=name)
    with jax.named_scope(name):
        return call(*ins)


def _sizes(q, v, g):
    b, seq, key_dim = q.shape
    _, hk, n, width = g.shape
    chunk = seq // n
    r = width // chunk
    return b, hk, r, key_dim // hk, v.shape[2] // (hk * r), n, chunk


@_jit_kernel
def _fwd_call(q, k, v, g, beta, *, per_step, keep, interpret):
    """``q``, ``k``: ``[b, seq, hk * dk]``; ``v``: ``[b, seq, hv * dv]``;
    ``g``, ``beta``: ``[b, hk, n, r * chunk]`` float32, ``seq = n * chunk``
    and ``n`` a multiple of ``per_step``.  Returns ``(o,)`` or, with
    ``keep``, ``(o, chunk start states [b, hv, n, dk, dv], inverses
    [b, hk, n, chunk, r * chunk])``, both float32."""
    b, hk, r, dk, dv, n, chunk = _sizes(q, v, g)
    qk_spec, v_spec, g_spec, s_spec, t_spec = _specs(
        r, dk, dv, chunk, per_step, lambda i: i)
    out_specs, out_shapes = [v_spec], [jax.ShapeDtypeStruct(v.shape, q.dtype)]
    if keep:
        out_specs += [s_spec, t_spec]
        out_shapes += [
            jax.ShapeDtypeStruct((b, hk * r, n, dk, dv), _F32),
            jax.ShapeDtypeStruct((b, hk, n, chunk, r * chunk), _F32)]
    kernel = functools.partial(_fwd_kernel, chunk=chunk, keep=keep)
    return _launch(
        FWD_KERNEL, kernel, (b, hk, n // per_step),
        [qk_spec, qk_spec, v_spec, g_spec, g_spec], (q, k, v, g, beta),
        out_specs, out_shapes,
        scratch=[(r, dk, dv), (per_step, r * chunk)], interpret=interpret)


@_jit_kernel
def _bwd_call(q, k, v, g, beta, states, inverses, do, *, per_step,
              interpret):
    """The gradients of :func:`_fwd_call`'s five arguments, in their
    shapes; dq and dk summed over the value heads of a key head."""
    b, hk, r, dk, dv, n, chunk = _sizes(q, v, g)
    blocks = n // per_step
    qk_spec, v_spec, g_spec, s_spec, t_spec = _specs(
        r, dk, dv, chunk, per_step, lambda i: blocks - 1 - i)
    kernel = functools.partial(_bwd_kernel, chunk=chunk)
    return _launch(
        BWD_KERNEL, kernel, (b, hk, blocks),
        [qk_spec, qk_spec, v_spec, g_spec, g_spec, s_spec, t_spec, v_spec],
        (q, k, v, g, beta, states, inverses, do),
        [qk_spec, qk_spec, v_spec, g_spec, g_spec],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
        scratch=[(r, dk, dv), (per_step, r * chunk), (per_step, r * chunk)],
        interpret=interpret)


def _count_chunks(kernel, q, v, g, interpret):
    """The trace-time counter: once for every kernel call that is traced,
    the chunks it walks (of every value head)."""
    from .. import metrics

    b, hk, r, _, _, n, _ = _sizes(q, v, g)
    metrics.record_gdn_scan_chunks(
        kernel, "interpret" if interpret else "mosaic", b * hk * r * n)


@functools.lru_cache(maxsize=None)
def _scan_fn(per_step, interpret):
    kw = dict(per_step=per_step, interpret=interpret)

    def forward(keep, q, k, v, g, beta):
        _count_chunks("fwd", q, v, g, interpret)
        return _fwd_call(q, k, v, g, beta, keep=keep, **kw)

    @jax.custom_vjp
    def f(q, k, v, g, beta):
        return forward(False, q, k, v, g, beta)[0]

    def fwd(q, k, v, g, beta):
        o, states, inverses = forward(True, q, k, v, g, beta)
        # what the forward kernel wrote and the backward kernel reads: a
        # checkpoint whose policy saves these names runs the kernel once
        o = checkpoint_name(o, GDN_OUT)
        states = checkpoint_name(states, GDN_STATES)
        inverses = checkpoint_name(inverses, GDN_INVERSES)
        _count_residuals("gdn_scan", o, states, inverses)
        q, k, v, g, beta = (checkpoint_name(x, GDN_IN)
                            for x in (q, k, v, g, beta))
        return o, (q, k, v, g, beta, states, inverses)

    def bwd(res, do):
        _count_chunks("bwd", res[0], res[2], res[3], interpret)
        return tuple(_bwd_call(*res, do, **kw))

    f.defvjp(fwd, bwd)
    return f


def residual_bytes(b: int, s: int, hv: int, dk: int, dv: int, chunk: int,
                   itemsize: int) -> int:
    """Bytes a differentiated call at these sizes keeps for its backward
    kernel beyond its inputs (what the ``fwd`` rule counts): ``o``, a
    float32 ``dk x dv`` state a chunk and value head, and the chunks'
    float32 inverses."""
    return b * hv * (s * dv * itemsize + -(-s // chunk) * dk * dv * 4
                     + s * chunk * 4)


def _check_tiling(dtype, dk, dv, chunk):
    """What Mosaic's tiling asks of the blocks: a head's columns are whole
    lane tiles of ``[b, seq, heads * d]`` and a chunk is whole sublane
    tiles of the operands' dtype."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    if dk % 128 or dv % 128 or chunk % sublanes:
        raise ValueError(
            f"gated_delta_rule on a TPU takes head sizes that are multiples "
            f"of 128 and chunks that are multiples of {sublanes} rows of "
            f"{jnp.dtype(dtype).name}; got dk {dk}, dv {dv}, chunk {chunk}")


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     interpret: Optional[bool] = None):
    """Chunked gated delta rule, differentiable in every argument.

    Args:
      q, k: ``[batch, seq, key_heads, dk]``, already normalised and scaled
        as the model wants them.
      v: ``[batch, seq, value_heads, dv]``; ``value_heads`` is a multiple
        of ``key_heads`` and key head ``i`` serves value heads
        ``i * r .. i * r + r - 1``.
      g: ``[batch, seq, value_heads]``, the log of each step's decay
        (``<= 0``); taken in float32.
      beta: ``[batch, seq, value_heads]``, in ``(0, 1)``.
      chunk: tokens a chunk.  A sequence that is not a multiple of the
        kernels' block (``CHUNKS_PER_STEP`` chunks, or all of a shorter
        sequence's) is padded at its end with rows that write nothing
        (``k = 0``, ``beta = 0``, ``g = 0``) and are cut off again.
      interpret: run the kernels in Pallas interpreter mode; by default on
        every platform but a TPU.

    Returns ``o``: ``[batch, seq, value_heads, dv]`` in ``q``'s dtype.
    """
    if chunk < 1:
        raise ValueError(f"chunk {chunk} is not a positive number of tokens")
    r = _value_heads_per_key_head(k, v)
    b, seq, hk, dk = k.shape
    hv, dv = v.shape[2:]
    interpret = _resolve_interpret(interpret)
    if not interpret:
        _check_tiling(q.dtype, dk, dv, chunk)
    n = -(-seq // chunk)
    per_step = min(CHUNKS_PER_STEP, n)
    n = -(-n // per_step) * per_step
    pad = n * chunk - seq

    def rows(x):
        """``[b, seq, h, d]`` -> ``[b, n * chunk, h * d]``."""
        x = x.astype(q.dtype).reshape(b, seq, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def lanes(x):
        """``[b, seq, hv]`` -> ``[b, hk, n, r * chunk]`` float32: a chunk
        of a key head's value heads side by side."""
        x = jnp.swapaxes(x.astype(_F32), 1, 2)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
        x = x.reshape(b, hk, r, n, chunk).transpose(0, 1, 3, 2, 4)
        return x.reshape(b, hk, n, r * chunk)

    with jax.named_scope(SCAN_SCOPE):
        o = _scan_fn(per_step, interpret)(
            rows(q), rows(k), rows(v), lanes(g), lanes(beta))
        return o[:, :seq].reshape(b, seq, hv, dv)
