"""The gated delta rule (Gated DeltaNet linear attention) as a chunked scan.

Per value head, with a state ``S`` of keys by values (``[dk, dv]``,
``S_0 = 0``), a log decay ``g_t <= 0`` and a write strength ``beta_t``::

    S  <- exp(g_t) * S
    u  =  beta_t * (v_t - S^T k_t)
    S  <- S + k_t u^T
    o_t = S^T q_t

:func:`gated_delta_recurrence` is that recurrence, token by token, in
float32: the definition the chunked form is tested against.
:func:`gated_delta_rule` computes the same thing in chunks of ``chunk``
tokens so that nearly all of the work is matrix products.

The chunked form.  Inside a chunk that starts from state ``S_0`` write
``G_t`` for the sum of ``g`` up to and including row ``t``.  The ``u`` of
the chunk's rows solve a unit lower-triangular system (the WY / UT
transform)::

    A[t, j]  = -beta_t * exp(G_t - G_j) * (k_t . k_j)     (j < t, else 0)
    T        = (I - A)^-1
    U_hat    = T (beta * V)              W = T (beta * exp(G) * K)
    U        = U_hat - W S_0
    O        = (exp(G) * Q) S_0 + P U    P[t, j] = exp(G_t - G_j) (q_t . k_j), j <= t
    S_C      = exp(G_C) S_0 + (exp(G_C - G) * K)^T U

``I - A`` is unit lower triangular, and ``U_hat`` and ``W`` are one
triangular solve in float32 with the right-hand side ``[beta V | beta
exp(G) K]``; ``T`` is never formed.  (On a v5e the solve is faster than the
finite product ``(I + A)(I + A^2)(I + A^4)...`` of small matrix products,
26.2 against 30.7 ms a layer forward and backward at 8192 tokens, and ten
times closer to the float32 result: PERF.md, PR 26.)  Everything up to
``U_hat``, ``W`` and ``P`` is independent of the state and is computed for
all chunks at once (scope ``hvd_gdn_scan/local``); a ``lax.scan`` over the
chunks then carries ``S`` (scope ``hvd_gdn_scan/carry``): three products
with the state and one with ``P`` a chunk.

Precision.  The decays are float32 and in log space: every exponent that is
taken is ``<= 0`` (masked before ``exp``, not after).  ``S`` is carried in
float32.  The operands of the matrix products have the dtype of ``q``
(bfloat16 in the models, float32 in the tests) and accumulate in float32.

The backward pass is XLA's.  Each step of the scan is a
``jax.checkpoint``: it keeps only the state it started from, one ``[dk,
dv]`` float32 matrix a head a chunk (268 MB a layer at 8192 tokens, 32
heads, 128 x 128), and recomputes its products.  The chunk-local block
keeps what XLA's backward of it needs (the solve's result, ``U_hat``,
``W``, ``P`` and the decay matrix, about 0.5 GB a layer at that size):
recomputing it too cost 8.7 ms a layer of 30.8 on a v5e and saved no
memory at the step's peak (PERF.md, PR 26).  A model that recomputes
whole layers holds all of this for one layer at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SCAN_SCOPE = "hvd_gdn_scan"
_F32 = jnp.float32


def gated_delta_recurrence(q, k, v, g, beta):
    """The recurrence itself, a ``lax.scan`` over tokens in float32.

    Args and result as :func:`gated_delta_rule`."""
    q, k = _serve_value_heads(q, k, v.shape[2])
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


def _serve_value_heads(q, k, value_heads: int):
    """Key head ``i`` serves value heads ``i * r .. i * r + r - 1``."""
    key_heads = k.shape[2]
    if value_heads % key_heads:
        raise ValueError(f"{value_heads} value heads are not a multiple of "
                         f"{key_heads} key heads")
    r = value_heads // key_heads
    if r == 1:
        return q, k
    return jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)


def _chunk_local(q, k, v, gamma, beta):
    """What a chunk needs that does not depend on the state it starts from.
    ``q``, ``k``, ``v``: ``[b, h, n, c, d]``; ``gamma`` (the decay summed
    from the chunk's start) and ``beta``: ``[b, h, n, c]`` float32.
    Returns ``(u_hat [.., c, dv], w [.., c, dk], p [.., c, c])`` in the
    operands' dtype."""
    dtype = q.dtype
    c, dv = q.shape[-2], v.shape[-1]
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # exp(G_t - G_j) where j <= t and 0 elsewhere; the exponent is masked
    # first, so nothing above the diagonal is ever exponentiated
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(rows >= cols, diff, -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, preferred_element_type=_F32)
    unit_lower = jnp.where(rows > cols, beta[..., :, None] * decay * kk,
                           0.0) + jnp.eye(c, dtype=_F32)    # I - A
    k32 = k.astype(_F32)
    rhs = jnp.concatenate(
        [v.astype(_F32), k32 * jnp.exp(gamma)[..., None]],
        axis=-1) * beta[..., None]
    solved = lax.linalg.triangular_solve(
        unit_lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    qk = jnp.einsum("...id,...jd->...ij", q, k, preferred_element_type=_F32)
    return (solved[..., :dv].astype(dtype), solved[..., dv:].astype(dtype),
            (decay * qk).astype(dtype))


def _chunk_step(s, xs):
    """One chunk of every head from its start state ``s`` (``[b, h, dk,
    dv]`` float32): the chunk's outputs and the state it leaves."""
    q_in, k_out, u_hat, w, p, decay_all = xs
    dtype = q_in.dtype
    s_op = s.astype(dtype)
    u = u_hat.astype(_F32) - jnp.einsum(
        "bhck,bhkv->bhcv", w, s_op, preferred_element_type=_F32)
    u_op = u.astype(dtype)
    o = jnp.einsum("bhck,bhkv->bhcv", q_in, s_op,
                   preferred_element_type=_F32) \
        + jnp.einsum("bhij,bhjv->bhiv", p, u_op, preferred_element_type=_F32)
    s = s * decay_all[..., None, None] + jnp.einsum(
        "bhck,bhcv->bhkv", k_out, u_op, preferred_element_type=_F32)
    return s, o.astype(dtype)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
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
      chunk: tokens a chunk.  A sequence that is not a
        multiple of it is padded at its end with rows that write nothing
        (``k = 0``, ``beta = 0``, ``g = 0``) and are cut off again.

    Returns ``o``: ``[batch, seq, value_heads, dv]`` in ``q``'s dtype.
    """
    if chunk < 1:
        raise ValueError(f"chunk {chunk} is not a positive number of tokens")
    b, seq, h, dv = v.shape
    with jax.named_scope(SCAN_SCOPE):
        q, k = _serve_value_heads(q, k, h)
        dtype = q.dtype
        pad = -seq % chunk
        n = (seq + pad) // chunk

        def chunks(x):
            """``[b, seq, h, ...]`` -> ``[b, h, n, chunk, ...]``."""
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            x = x.reshape(b, n, chunk, *x.shape[2:])
            return jnp.moveaxis(x, 3, 1)

        q, k, v = chunks(q), chunks(k), chunks(v.astype(dtype))
        g, beta = chunks(g.astype(_F32)), chunks(beta.astype(_F32))
        gamma = jnp.cumsum(g, axis=-1)                     # [b, h, n, c]

        with jax.named_scope("local"):
            u_hat, w, p = _chunk_local(q, k, v, gamma, beta)
            total = gamma[..., -1:]
            q_in = (q.astype(_F32) * jnp.exp(gamma)[..., None]).astype(dtype)
            k_out = (k.astype(_F32)
                     * jnp.exp(total - gamma)[..., None]).astype(dtype)
        with jax.named_scope("carry"):
            xs = tuple(jnp.moveaxis(x, 2, 0) for x in (
                q_in, k_out, u_hat, w, p, jnp.exp(total[..., 0])))
            s0 = jnp.zeros((b, h, k.shape[-1], dv), _F32)
            _, o = lax.scan(jax.checkpoint(_chunk_step), s0, xs)
        o = jnp.moveaxis(o, 0, 2)                          # [b, h, n, c, dv]
        o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
        return o[:, :seq]
