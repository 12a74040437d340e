"""Decoder LM family: causal correctness, training, and the sequence-
parallel composition (long-context first-class; the reference ships no
model code, SURVEY §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.gpt import gpt_tiny, next_token_loss


def test_causality(hvd_init, rng):
    """Changing a future token must not change past logits."""
    model = gpt_tiny(dtype=jnp.float32)
    ids = rng.integers(0, 1024, size=(2, 32)).astype(np.int32)
    v = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))

    with jax.default_device(jax.devices("cpu")[0]):
        out1 = model.apply(v, jnp.asarray(ids))
        ids2 = ids.copy()
        ids2[:, 20:] = (ids2[:, 20:] + 7) % 1024
        out2 = model.apply(v, jnp.asarray(ids2))
    np.testing.assert_allclose(np.asarray(out1[:, :20]),
                               np.asarray(out2[:, :20]),
                               rtol=1e-4, atol=1e-4)
    assert not np.allclose(np.asarray(out1[:, 20:]),
                           np.asarray(out2[:, 20:]), atol=1e-3)


def test_lm_training_loss_decreases(hvd_init, rng):
    """Full DP training step over the 8-device mesh on next-token loss."""
    from horovod_tpu.training import (
        TrainState, init_train_state, make_train_step, shard_batch,
    )

    model = gpt_tiny(dtype=jnp.float32, num_layers=2)
    opt = optax.adam(1e-3)
    step = make_train_step(
        apply_fn=lambda vars_, x, train=True: model.apply(vars_, x),
        loss_fn=next_token_loss,
        optimizer=opt,
    )
    state = init_train_state(
        model, opt, jnp.zeros((2, 16), jnp.int32),
    )
    ids = rng.integers(0, 1024, size=(16, 16)).astype(np.int32)
    x = shard_batch(ids)

    losses = []
    for _ in range(20):
        state, loss = step(state, x, x)
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0], losses


def test_sequence_parallel_gpt_matches_single_device(hvd_init, rng):
    """GPT forward with ring attention over a sequence-sharded mesh ==
    single-device forward (global positions via seq_offset)."""
    from horovod_tpu.parallel.ring_attention import ring_attention

    seq = 64
    n = 8
    ids = rng.integers(0, 1024, size=(2, seq)).astype(np.int32)

    plain = gpt_tiny(dtype=jnp.float32, num_layers=2)
    v = plain.init(jax.random.PRNGKey(0), jnp.asarray(ids))

    sp_model = gpt_tiny(
        dtype=jnp.float32, num_layers=2,
        attention_fn=lambda q, k, v_, m: ring_attention(
            q, k, v_, causal=True),
    )

    @hvd.spmd(in_specs=(P(), P(None, hvd.AXIS)), out_specs=P(None, hvd.AXIS))
    def fwd(vars_, ids_shard):
        off = hvd.rank() * (seq // n)
        return sp_model.apply(vars_, ids_shard, seq_offset=off)

    out_sp = np.asarray(fwd(v, ids))
    with jax.default_device(jax.devices("cpu")[0]):
        out_ref = np.asarray(plain.apply(v, jnp.asarray(ids)))
    np.testing.assert_allclose(out_sp, out_ref, rtol=2e-3, atol=2e-3)


# -- next_token_loss -----------------------------------------------------------

LOSS_SHAPES = [(1, 2, 7), (1, 33, 257), (3, 16, 1024)]


def _two_line_loss(logits, ids):
    """The definition: log-probabilities of all but the last position, the
    label's picked out of each.  ``next_token_loss`` computed it this way
    until PR 27 (its backward pass scatters into a zero-filled
    ``[b, s - 1, V]`` buffer and pads the slice back)."""
    logp = jax.nn.log_softmax(logits[:, :-1])
    ll = jnp.take_along_axis(logp, ids[:, 1:][..., None], axis=-1)
    return -jnp.mean(ll)


def _loss_case(shape, seed=0):
    """Float32 logits with a wide spread and ids that repeat inside every
    row (the same label at several positions, and a position whose label
    is its own token)."""
    b, s, v = shape
    rng = np.random.default_rng(seed + 7 * s)
    logits = (4.0 * rng.standard_normal(shape)).astype(np.float32)
    ids = rng.integers(0, v, size=(b, s)).astype(np.int32)
    ids[:, s // 2:] = ids[:, :1]
    return jnp.asarray(logits), ids


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("shape", LOSS_SHAPES, ids=str)
def test_next_token_loss_is_the_two_line_definition(shape):
    logits, ids = _loss_case(shape)
    got = next_token_loss(logits, jnp.asarray(ids))
    want = _two_line_loss(logits, jnp.asarray(ids))
    assert got.dtype == jnp.float32 and got.shape == ()
    # a float32 difference is exact to its operands' size, not its own: at
    # (1, 2, 7) the label is the row's largest logit, logsumexp 5.1 and
    # the loss 0.11, so 1e-6 of the loss is a fifth of float32's step at 5
    lse = float(jnp.mean(jax.nn.logsumexp(logits, axis=-1)))
    assert abs(float(got) - float(want)) < 1e-6 * max(float(want), lse)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("shape", LOSS_SHAPES, ids=str)
def test_next_token_loss_gradient_is_the_definitions(shape, jit):
    logits, ids = _loss_case(shape)
    wrap = jax.jit if jit else (lambda f: f)
    got = wrap(jax.grad(next_token_loss))(logits, jnp.asarray(ids))
    want = jax.grad(_two_line_loss)(logits, jnp.asarray(ids))
    assert got.dtype == jnp.float32 and got.shape == logits.shape
    assert _rel(got, want) < 1e-6
    # element by element as well, against the largest element
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        < 1e-6 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("shape", LOSS_SHAPES, ids=str)
def test_the_last_positions_cotangent_is_exactly_zero(shape):
    logits, ids = _loss_case(shape)
    grad = np.asarray(jax.grad(next_token_loss)(logits, jnp.asarray(ids)))
    assert not grad[:, -1].any()
    assert grad[:, :-1].any(axis=-1).all()
    # every row of a softmax's cotangent sums to nothing
    assert np.abs(grad.sum(-1)).max() < 1e-6 / (shape[0] * (shape[1] - 1))


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint16], ids=str)
def test_next_token_loss_takes_ids_of_any_integer_type(dtype):
    logits, ids = _loss_case((3, 16, 1024))
    want = _two_line_loss(logits, jnp.asarray(ids))
    got, grad = jax.value_and_grad(next_token_loss)(
        logits, jnp.asarray(ids.astype(dtype)))
    assert _rel(got, want) < 1e-6
    assert _rel(grad, jax.grad(_two_line_loss)(logits, jnp.asarray(ids))) \
        < 1e-6


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_a_sequence_shards_call_keeps_the_within_shard_meaning(shards):
    """``examples/gpt_synthetic_benchmark.py``'s sequence-parallel branch
    calls the loss on each rank's ``[b, s / n]`` shard of logits and ids and
    averages over ranks: each shard predicts inside itself and drops the
    prediction across its boundary."""
    logits, ids = _loss_case((2, 32, 257))
    local = 32 // shards
    for r in range(shards):
        cut = slice(r * local, (r + 1) * local)
        got, grad = jax.value_and_grad(next_token_loss)(
            logits[:, cut], jnp.asarray(ids[:, cut]))
        want, want_grad = jax.value_and_grad(_two_line_loss)(
            logits[:, cut], jnp.asarray(ids[:, cut]))
        assert _rel(got, want) < 1e-6
        assert _rel(grad, want_grad) < 1e-6
        assert not np.asarray(grad)[:, -1].any()
