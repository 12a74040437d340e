"""Pallas flash-attention kernels vs dense oracles.

Runs the kernels in interpreter mode (forced, so the tests are exact on
the CPU mesh regardless of which backends are present): local fwd/bwd,
global-position offsets, the ring-attention pallas path (fwd + grad), and
Ulysses with the flash local step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.flash_attention import flash_attention, mha_partial
from horovod_tpu.parallel.ring_attention import (
    ring_attention, ulysses_attention,
)


def _dense(q, k, v, causal=False, q_off=0, kv_off=0):
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qp = q_off + np.arange(q.shape[1])
        kp = kv_off + np.arange(k.shape[1])
        s = np.where((qp[:, None] >= kp[None, :])[None, None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture()
def qkv(rng):
    b, s, h, d = 2, 64, 2, 16
    mk = lambda: rng.normal(size=(b, s, h, d)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.fixture(autouse=True)
def _on_cpu():
    """Local (non-mesh) kernel tests must be exact f32: pin the default
    device to CPU — with a TPU plugin present the interpreted kernels would
    otherwise execute their jnp ops on the TPU at bf16 matmul precision."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_matches_dense(qkv, causal):
    q, k, v = qkv
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, causal),
                               rtol=2e-4, atol=2e-4)


def test_flash_non_dividing_seq_fits_blocks(rng):
    """seq 192 with the default 128 blocks used to raise; blocks now shrink
    to the largest divisor (96) and results stay exact (ADVICE r1)."""
    b, s, h, d = 1, 192, 2, 16
    q, k, v = (rng.normal(size=(b, s, h, d)).astype(np.float32)
               for _ in range(3))
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, True),
                               rtol=2e-4, atol=2e-4)


def test_flash_offsets_match_dense(qkv):
    """Causal masking in global positions: a 32-row q shard starting at
    position 32 against the full kv sequence."""
    q, k, v = qkv
    qs = q[:, :32]
    out = flash_attention(jnp.asarray(qs), jnp.asarray(k), jnp.asarray(v),
                          causal=True, q_offset=32, kv_offset=0,
                          block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _dense(qs, k, v, True, q_off=32),
        rtol=2e-4, atol=2e-4,
    )


def test_flash_fully_masked_rows_are_finite(qkv):
    """A kv shard strictly in the future of every q row: the partial triple
    must come back all-zero (l == 0), not NaN — this is the ring hop case."""
    q, k, v = qkv
    qt = jnp.swapaxes(jnp.asarray(q[:, :16]), 1, 2)
    kt = jnp.swapaxes(jnp.asarray(k[:, :16]), 1, 2)
    vt = jnp.swapaxes(jnp.asarray(v[:, :16]), 1, 2)
    o, m, l = mha_partial(qt, kt, vt, 0, 1024, causal=True,
                          scale=0.25, block_q=16, block_k=16,
                          interpret=True)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_array_equal(np.asarray(l), 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_dense(qkv, causal):
    q, k, v = (jnp.asarray(x) for x in qkv)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=16,
                                block_k=16, interpret=True) ** 2).sum()

    def _dense_jnp(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            pos = jnp.arange(q.shape[1])
            s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s,
                          -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def loss_dense(q, k, v):
        return (_dense_jnp(q, k, v) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        scale = max(float(jnp.max(jnp.abs(b))), 1.0)
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_pallas_matches_dense(hvd_init, rng, causal):
    b, s_local, h, d = 2, 8, 2, 16
    n = 8
    mk = lambda: rng.normal(size=(b, s_local * n, h, d)).astype(np.float32)
    q, k, v = mk(), mk(), mk()

    @hvd.spmd(in_specs=(P(None, hvd.AXIS),) * 3, out_specs=P(None, hvd.AXIS))
    def step(q, k, v):
        return ring_attention(q, k, v, causal=causal, impl="pallas",
                              block_q=8, block_k=8)

    out = np.asarray(step(q, k, v))
    np.testing.assert_allclose(out, _dense(q, k, v, causal),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_pallas_grad_matches_xla(hvd_init, rng, causal):
    """The pallas ring backward (rotating dk/dv accumulators) against the
    XLA ring autodiff."""
    b, s_local, h, d = 1, 8, 2, 8
    n = 8
    mk = lambda: rng.normal(size=(b, s_local * n, h, d)).astype(np.float32)
    q, k, v = mk(), mk(), mk()
    dout = rng.normal(size=(b, s_local * n, h, d)).astype(np.float32)

    def make_loss(impl):
        @hvd.spmd(in_specs=(P(None, hvd.AXIS),) * 4, out_specs=P())
        def loss(q, k, v, g):
            out = ring_attention(q, k, v, causal=causal, impl=impl,
                                 block_q=8, block_k=8)
            # weighted sum -> cotangent g; psum for the global scalar
            from horovod_tpu.ops import collectives
            return collectives.allreduce((out * g).sum(), op=hvd.Sum)
        return loss

    g_pallas = jax.grad(make_loss("pallas"), argnums=(0, 1, 2))(
        q, k, v, dout)
    g_xla = jax.grad(make_loss("xla"), argnums=(0, 1, 2))(q, k, v, dout)
    for a, b_ in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_pallas_matches_dense(hvd_init, rng, causal):
    b, s_local, h, d = 2, 8, 8, 16
    n = 8
    mk = lambda: rng.normal(size=(b, s_local * n, h, d)).astype(np.float32)
    q, k, v = mk(), mk(), mk()

    @hvd.spmd(in_specs=(P(None, hvd.AXIS),) * 3, out_specs=P(None, hvd.AXIS))
    def step(q, k, v):
        return ulysses_attention(q, k, v, causal=causal, impl="pallas")

    out = np.asarray(step(q, k, v))
    np.testing.assert_allclose(out, _dense(q, k, v, causal),
                               rtol=2e-3, atol=2e-3)


def test_on_tpu_propagates_a_backend_error(monkeypatch):
    """A backend that cannot come up must not read as "not a TPU": that
    would turn a broken chip into a silent interpreter-mode run."""
    from horovod_tpu.ops import flash_attention as fa

    hvd.shutdown()

    def broken(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(fa.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        fa._on_tpu()
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        fa._resolve_interpret(None)


def test_on_tpu_reads_the_mesh_platform(hvd_init):
    """The CPU test mesh — and only a CPU mesh — interprets."""
    from horovod_tpu.ops import flash_attention as fa

    assert fa._on_tpu() is False
    assert fa._resolve_interpret(None) is True

