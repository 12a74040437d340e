"""``ops.gated_delta.gated_delta_rule``: the chunked form against the
token-by-token recurrence, forward and gradients, for sequence lengths that
are and are not a multiple of the chunk, with strong and weak decay and
with key heads that serve several value heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.gated_delta import (gated_delta_recurrence,
                                         gated_delta_rule)


def _inputs(seed, b, t, hk, hv, dk, dv, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, hk, dk))
    k = jax.random.normal(ks[1], (b, t, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    # log decays from -0.02 to -12 a token: heads that remember a whole
    # chunk and heads that forget within two tokens
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, hv), minval=-4.0,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("seq,chunk", [(128, 16), (100, 16), (7, 16),
                                       (64, 64), (130, 64), (100, 24)])
def test_chunked_equals_the_recurrence(seq, chunk):
    args = _inputs(seq, 2, seq, 2, 4, 16, 8)
    want = gated_delta_recurrence(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (2, seq, 4, 8)
    assert float(jnp.max(jnp.abs(got - want))) \
        < 2e-6 * max(1.0, float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("seq", [96, 50])
def test_gradients_equal_the_recurrences(seq):
    args = _inputs(seq + 1, 1, seq, 2, 4, 16, 8)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a))),
                        argnums=(0, 1, 2, 3, 4))

    want = through(gated_delta_recurrence)(*args)
    got = through(lambda *a: gated_delta_rule(*a, chunk=16))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-5 * float(jnp.max(jnp.abs(b))), name


def test_state_carries_across_chunks():
    """With no decay and unit write strength the rule stores ``v`` under
    ``k``: a key written in the first chunk is read back in the last."""
    t, dk = 64, 16
    k = jnp.eye(dk)[jnp.arange(t) % dk][None, :, None, :]   # [1, t, 1, dk]
    v = jax.random.normal(jax.random.PRNGKey(0), (1, t, 1, 4))
    q = jnp.roll(k, -1, axis=1) * 0 + k                     # read own key
    g = jnp.zeros((1, t, 1))
    beta = jnp.ones((1, t, 1))
    o = gated_delta_rule(q, k, v, g, beta, chunk=16)
    # each token reads the value just written under its own key
    np.testing.assert_allclose(o, v, atol=1e-5)
    # and a query for token 3's key at the end reads what token 51 (the
    # last to write that key) stored there
    q_last = q.at[0, -1, 0].set(k[0, 3, 0])
    o = gated_delta_rule(q_last, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(o[0, -1, 0], v[0, 51, 0], atol=1e-5)


def test_bfloat16_operands_stay_close_to_the_float32_recurrence():
    args = _inputs(3, 1, 256, 2, 4, 32, 32)
    want = gated_delta_recurrence(*args)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got = gated_delta_rule(*low, chunk=64)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.03 * float(jnp.max(jnp.abs(want)))


def test_bad_arguments_are_named():
    args = _inputs(0, 1, 8, 2, 4, 8, 8)
    with pytest.raises(ValueError, match="positive"):
        gated_delta_rule(*args, chunk=0)
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="not a multiple"):
        gated_delta_rule(q[:, :, :1].repeat(3, 2), k[:, :, :1].repeat(3, 2),
                         v, g, beta)
