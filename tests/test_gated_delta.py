"""``ops.gated_delta.gated_delta_rule``: the Pallas kernels (in interpreter
mode here) against the token-by-token recurrence, forward and gradients,
for sequence lengths that are and are not a multiple of the chunk and of
the kernels' block of chunks, with strong and weak decay and with key heads
that serve one or several value heads (read by index map, dq and dk summed
in the backward kernel)."""

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


# a block is CHUNKS_PER_STEP = 8 chunks: 300 tokens in chunks of 16 are
# three blocks, the last one 84 rows of padding; 1030 in chunks of 64 two
@pytest.mark.parametrize("seq,chunk,hk", [
    (128, 16, 2), (100, 16, 2), (7, 16, 2), (64, 64, 2), (130, 64, 2),
    (100, 24, 2), (300, 16, 2), (1030, 64, 2), (300, 16, 4), (129, 16, 1)])
def test_chunked_equals_the_recurrence(seq, chunk, hk):
    args = _inputs(seq, 2, seq, hk, 4, 16, 8)
    want = gated_delta_recurrence(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (2, seq, 4, 8)
    assert float(jnp.max(jnp.abs(got - want))) \
        < 2e-6 * max(1.0, float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("seq,hk", [(96, 2), (50, 2), (300, 2), (150, 4),
                                    (140, 1)])
def test_gradients_equal_the_recurrences(seq, hk):
    """q, k, v, g and beta; with fewer key heads than value heads dq and dk
    are the sums over the value heads a key head serves."""
    args = _inputs(seq + 1, 1, seq, hk, 4, 16, 8)

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


def _bfloat16(args):
    return tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]


def test_bfloat16_operands_stay_close_to_the_float32_recurrence():
    args = _inputs(3, 1, 256, 2, 4, 32, 32)
    want = gated_delta_recurrence(*args)
    got = gated_delta_rule(*_bfloat16(args), chunk=64)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < 0.03 * float(jnp.max(jnp.abs(want)))


def test_bfloat16_gradients_stay_close_to_the_float32_recurrences():
    """The backward kernel with bfloat16 operands: gradients come back in
    their arguments' dtypes, 3% of each one's largest element from the
    float32 recurrence's."""
    args = _inputs(4, 1, 192, 2, 4, 32, 32)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 192, 4, 32))

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                        argnums=(0, 1, 2, 3, 4))

    want = through(gated_delta_recurrence)(*args)
    low = _bfloat16(args)
    got = through(lambda *a: gated_delta_rule(*a, chunk=64))(*low)
    for name, a, b, arg in zip("q k v g beta".split(), got, want, low):
        assert a.dtype == arg.dtype, name
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        assert err < 0.03 * float(jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_plain_call_equals_the_differentiated_calls_output(dtype):
    """The plain call writes no chunk states, the differentiated forward
    call does: one kernel body, so the outputs agree bit for bit."""
    args = _inputs(5, 1, 200, 2, 4, 16, 8, dtype)
    fn = lambda *a: gated_delta_rule(*a, chunk=16)  # noqa: E731
    plain = fn(*args)
    kept, _ = jax.vjp(fn, *args)
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(kept, np.float32))


def test_padding_rows_write_nothing():
    """A sequence cut short reads what the longer one read over the same
    tokens: the rows that pad the last block leave the state alone, and
    tokens after a row do not reach back to it."""
    args = _inputs(6, 1, 200, 2, 4, 16, 8)
    whole = gated_delta_rule(*args, chunk=16)
    cut = gated_delta_rule(*(a[:, :150] for a in args), chunk=16)
    np.testing.assert_allclose(cut, whole[:, :150], rtol=0, atol=1e-6)
    # and they take no gradient: d/dv of a sum over the kept rows is zero
    # on the rows that were cut
    dv = jax.grad(lambda v: jnp.sum(gated_delta_rule(
        args[0], args[1], v, *args[3:], chunk=16)[:, :150]))(args[2])
    assert float(jnp.max(jnp.abs(dv[:, 150:]))) == 0.0


def test_chunks_counter_counts_once_a_traced_call(monkeypatch):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read():
        got = {}
        for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_gdn_scan_chunks_traced_total", {}).get("samples", []):
            got[(s["labels"]["kernel"], s["labels"]["path"])] = s["value"]
        return got

    args = _inputs(7, 2, 40, 2, 4, 16, 8)
    fn = jax.jit(jax.grad(lambda *a: jnp.sum(
        gated_delta_rule(*a, chunk=16, interpret=True))))
    before = read()
    fn(*args)
    fn(*args)  # a cache hit: the counter moves per trace, not per call
    delta = {k: v - before.get(k, 0) for k, v in read().items()}
    # 3 chunks of 16 cover 40 tokens; 2 rows x 4 value heads
    assert delta == {("fwd", "interpret"): 24, ("bwd", "interpret"): 24}


def test_bad_arguments_are_named():
    args = _inputs(0, 1, 8, 2, 4, 8, 8)
    with pytest.raises(ValueError, match="positive"):
        gated_delta_rule(*args, chunk=0)
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="not a multiple"):
        gated_delta_rule(q[:, :, :1].repeat(3, 2), k[:, :, :1].repeat(3, 2),
                         v, g, beta)
    # what the compiled kernels cannot tile is named before anything lowers
    with pytest.raises(ValueError, match="dk 8, dv 8, chunk 64"):
        gated_delta_rule(*args, interpret=False)
