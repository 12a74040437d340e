"""Pallas TPU flash-attention kernels.

The reference framework contains no attention code at all (SURVEY §5:
sequence parallelism "absent"); long-context support is a first-class goal
of the TPU build, and this module is its compute core: a blockwise
online-softmax ("flash") attention kernel family written in Pallas so the
hot loop runs out of VMEM and the q·kᵀ / p·v contractions land on the MXU.

Kernel structure: the kv loop is the innermost *grid* dimension (not a
``fori_loop``) with the streaming accumulators in VMEM scratch that
persists across grid steps — this lets the Mosaic pipeline overlap each
kv-block DMA with the previous block's compute, which is ~2x over the
loop-over-resident-kv formulation.

Three public entry points:

* :func:`flash_attention` — full (normalized) local attention with a
  custom VJP whose backward pass is also Pallas kernels.  Drop-in
  ``attention_fn`` for the flax models and the local step of Ulysses.
* :func:`mha_partial` — unnormalized streaming triple ``(o, m, l)`` for one
  q-shard × kv-shard pair with *global-position* causal masking via
  dynamic offsets; this is the per-hop block compute of ring attention
  (the offsets arrive as scalar-prefetch operands, so the ring step can
  pass traced ``lax.axis_index``-derived values).
* :func:`mha_bwd_dq` / :func:`mha_bwd_dkv` — backward blocks with the same
  offset masking, used by the ring attention backward rotation.

All kernels take/return the ``[batch, heads, seq, head_dim]`` layout; the
callers transpose from the model-facing ``[batch, seq, heads, head_dim]``.

On the CPU test mesh, and only there, the kernels run in Pallas
interpreter mode, which keeps every test oracle-checkable on the
8-device virtual slice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf: keeps exp()-of-differences NaN-free for fully
# masked rows (exp(NEG_INF - NEG_INF) = 1, then zeroed by the mask select).
NEG_INF = -1e30

# 512x512 won a 128..1024 sweep at one shape (b4 h12 d64 s2048) on an
# earlier chip path; root PERF.md lists it as a hypothesis to re-measure.
# _fit_block shrinks automatically for shorter sequences
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

_DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")

# The three kernels' names: each ``pallas_call``'s ``name=`` and the
# ``jax.named_scope`` it runs under, so a device trace tells forward, dq and
# dkv apart (docs/profiling.md; benchmarks/layer_metrics/flash_*_ms.py).
FWD_KERNEL = "hvd_flash_fwd"
DQ_KERNEL = "hvd_flash_dq"
DKV_KERNEL = "hvd_flash_dkv"


def _on_tpu() -> bool:
    """True if the devices the framework runs on are TPUs.

    The mesh devices, not ``jax.devices()[0]``, are authoritative: the test
    harness runs an 8-device *CPU* mesh even when a TPU backend is present
    (conftest.py), and there the kernels must take the interpreter path.
    A backend that fails to come up raises here: turning that into
    interpreter mode would hide a broken chip behind a slow correct run.
    """
    from .. import core

    dev = (core.mesh().devices.flat[0] if core.is_initialized()
           else jax.devices()[0])
    return dev.platform == "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def _offsets(q_offset, kv_offset):
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(())
    kv_offset = jnp.asarray(kv_offset, jnp.int32).reshape(())
    return jnp.stack([q_offset, kv_offset])


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=_DIM_SEMANTICS)


def _fit_block(seq: int, cap: int) -> int:
    """Largest divisor of ``seq`` that is <= ``cap``, preferring a
    lane-aligned multiple of 8 (MXU tiling) — but only when alignment
    doesn't collapse the block (e.g. seq 136: plain 68 beats aligned 8)."""
    cap = min(cap, seq)
    aligned = next(
        (b for b in range(cap, 0, -1) if seq % b == 0 and b % 8 == 0), 0
    )
    plain = next((b for b in range(cap, 0, -1) if seq % b == 0), 1)
    return aligned if aligned * 4 >= plain else plain


def _check_blocks(sq, sk, block_q, block_k):
    """Fit block sizes to the seq lengths: the grid must tile exactly (a
    non-dividing seq would silently truncate the grid and leave the tail
    of the output uninitialized), so shrink each block to the largest
    divisor of its seq length instead of erroring on shapes like 192/128."""
    return _fit_block(sq, block_q), _fit_block(sk, block_k)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                acc_ref, mi_ref, li_ref, *,
                causal, scale, normalize):
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]
    iq = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mi_ref[:] = jnp.full_like(mi_ref, NEG_INF)
        li_ref[:] = jnp.zeros_like(li_ref)

    def compute():
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = (q_off + iq * bq
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            k_pos = (kv_off + j * bk
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, NEG_INF)
        m_prev = mi_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        mi_ref[:] = m_new
        li_ref[:] = li_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Skip kv blocks strictly in the future of every row of this q block.
        pl.when(kv_off + j * bk <= q_off + iq * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _():
        acc = acc_ref[:]
        if normalize:
            acc = acc / jnp.maximum(li_ref[:], 1e-30)
        o_ref[0, 0] = acc.astype(o_ref.dtype)
        m_ref[0, 0] = mi_ref[:]
        l_ref[0, 0] = li_ref[:]


def _mha_fwd(q, k, v, offs, *, causal, scale, block_q, block_k,
             normalize, interpret):
    """q/k/v ``[b,h,s,d]``; returns ``(o, m, l)`` with m/l ``[b,h,sq,1]``."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k)
    interpret = _resolve_interpret(interpret)
    grid = (b, h, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, normalize=normalize,
    )
    out_dtype = q.dtype if normalize else jnp.float32
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, i, j, *_: (b_, h_, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, i, j, *_: (b_, h_, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), out_dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=FWD_KERNEL,
    )
    with jax.named_scope(FWD_KERNEL):
        return call(offs, q, k, v)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref, *, causal, scale):
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    iq = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    @pl.when(j == 0)
    def _():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def compute():
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = (q_off + iq * bq
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            k_pos = (kv_off + j * bk
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(mask, p, 0.0)
        dp = lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_acc_ref[:] += lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(kv_off + j * bk <= q_off + iq * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0, 0] = dq_acc_ref[:]


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                    causal, scale):
    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    ik = pl.program_id(2)
    i = pl.program_id(3)
    nq = pl.num_programs(3)
    q_off = offs_ref[0]
    kv_off = offs_ref[1]

    @pl.when(i == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def compute():
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        qb = q_ref[0, 0]
        dob = do_ref[0, 0]
        lseb = lse_ref[0, 0]
        deltab = delta_ref[0, 0]
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = (q_off + i * bq
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            k_pos = (kv_off + ik * bk
                     + lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lseb)
        if causal:
            p = jnp.where(mask, p, 0.0)
        dv_acc_ref[:] += lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - deltab) * scale
        dk_acc_ref[:] += lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Skip q blocks entirely before this kv block.
        pl.when(kv_off + ik * bk <= q_off + i * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc_ref[:]
        dv_ref[0, 0] = dv_acc_ref[:]


def _mha_bwd_dq(q, k, v, do, lse, delta, offs, *, causal, scale, block_q,
                block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k)
    interpret = _resolve_interpret(interpret)
    grid = (b, h, sq // block_q, sk // block_k)
    kernel = functools.partial(_bwd_dq_kernel, causal=causal, scale=scale)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, i, j, *_: (b_, h_, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, i, j, *_: (b_, h_, j, 0)),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=DQ_KERNEL,
    )
    with jax.named_scope(DQ_KERNEL):
        return call(offs, q, k, v, do, lse, delta)


def _mha_bwd_dkv(q, k, v, do, lse, delta, offs, *, causal, scale, block_q,
                 block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k)
    interpret = _resolve_interpret(interpret)
    grid = (b, h, sk // block_k, sq // block_q)
    kernel = functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, jk, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, jk, 0)),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, jk, i, *_: (b_, h_, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, jk, i, *_: (b_, h_, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, jk, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h_, jk, i, *_: (b_, h_, jk, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=DKV_KERNEL,
    )
    with jax.named_scope(DKV_KERNEL):
        return call(offs, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# ring building blocks (dynamic offsets, [b,h,s,d] layout)
# ---------------------------------------------------------------------------


def mha_partial(q, k, v, q_offset, kv_offset, *, causal, scale,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=None):
    """Unnormalized streaming triple ``(o[f32], m, l)`` for one q-shard ×
    kv-shard pair; offsets are *global positions* and may be traced.
    m/l come back ``[b,h,sq,1]`` so they broadcast against ``o``."""
    return _mha_fwd(
        q, k, v, _offsets(q_offset, kv_offset), causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, normalize=False,
        interpret=interpret,
    )


def mha_bwd_dq(q, k, v, do, lse, delta, q_offset, kv_offset, *, causal,
               scale, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
               interpret=None):
    """dq (f32) contribution of one kv shard; lse/delta are ``[b,h,sq,1]``."""
    return _mha_bwd_dq(
        q, k, v, do, lse, delta, _offsets(q_offset, kv_offset),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def mha_bwd_dkv(q, k, v, do, lse, delta, q_offset, kv_offset, *, causal,
                scale, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=None):
    """(dk, dv) (f32) contributions of one q shard to one kv shard."""
    return _mha_bwd_dkv(
        q, k, v, do, lse, delta, _offsets(q_offset, kv_offset),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# local flash attention with custom VJP
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_fn(causal, scale, block_q, block_k, interpret):
    kw = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)

    @jax.custom_vjp
    def f(q, k, v, offs):
        o, _, _ = _mha_fwd(q, k, v, offs, normalize=True, **kw)
        return o

    def fwd(q, k, v, offs):
        o, m, l = _mha_fwd(q, k, v, offs, normalize=True, **kw)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [b,h,sq,1]
        return o, (q, k, v, o, lse, offs)

    def bwd(res, do):
        q, k, v, o, lse, offs = res
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        dq = _mha_bwd_dq(q, k, v, do, lse, delta, offs, **kw)
        dk, dv = _mha_bwd_dkv(q, k, v, do, lse, delta, offs, **kw)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                np.zeros(offs.shape, dtype=jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    q_offset=0, kv_offset=0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """Flash attention over local shards, differentiable end to end.

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]`` (the model-facing layout
        used throughout :mod:`horovod_tpu.parallel`).
      causal: apply causal masking in global positions
        (``q_offset + i >= kv_offset + j``).
      scale: logit scale, default ``1/sqrt(head_dim)``.
      q_offset, kv_offset: global position of element 0 of the q / kv
        shards (used by sequence-parallel callers).

    Returns attention output, same shape/dtype as ``q``.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    fn = _flash_fn(bool(causal), float(scale), int(block_q), int(block_k),
                   _resolve_interpret(interpret))
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = fn(qt, kt, vt, _offsets(q_offset, kv_offset))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def softmax_attention(q, k, v, *, causal: bool = False,
                      scale: Optional[float] = None):
    """Plain (materialized) softmax attention in ``[b,s,h,d]`` layout —
    the XLA-fused reference path the flash kernels are checked against,
    shared by the Ulysses local step and the benchmarks' --attn xla
    mode.  XLA fuses the chain; memory is O(s^2)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    sl = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = q.shape[1]
        pos = jnp.arange(s)
        sl = jnp.where((pos[:, None] >= pos[None, :])[None, None], sl,
                       -jnp.inf)
    p = jax.nn.softmax(sl, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
