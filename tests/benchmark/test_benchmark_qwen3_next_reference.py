"""The plain reference of ``qwen3_next_80b_a3b`` against the program's
model, against itself in blocks, and under the float8 control (a file of its
own: ``test_benchmark_references.py`` is the benchmark's)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_qwen
from benchmarks.configs import qwen3_next_80b_a3b
from benchmarks.harness import check
from benchmarks.references import common, qwen3_next
from test_benchmark_references import (_control_numbers,
                                       _worst_relative_difference)

QWEN = benchmark_tiny_qwen.QWEN_TINY


def _qwen_batch(seed, rows=2, seq=96):
    return (np.random.default_rng(seed).integers(
        0, QWEN["vocab_size"], (rows, seq)).astype(np.int32),)


def test_qwen3_next_reference_matches_the_programs_model():
    """Names, shapes, loss and gradients, through the adapter the benchmark
    itself builds the program with.  (``tests/test_qwen3_next.py`` holds the
    same over two whole periods of eight layers.)"""
    from horovod_tpu.models.gpt import next_token_loss

    mix = {"arrays": [{"shape": [96]}]}
    model = qwen3_next_80b_a3b.program(QWEN, mix)["model"]
    params = common.unflatten(qwen3_next.seeded_weights(QWEN, 2 ** 31 + 7))
    (ids,) = _qwen_batch(0)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.asarray(ids))["params"]
    assert {k: v.shape for k, v in common.flatten(shapes).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()}
    want, want_grad = jax.value_and_grad(qwen3_next.loss_fn(QWEN))(params,
                                                                   ids)
    got, got_grad = jax.value_and_grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert _worst_relative_difference(got_grad, want_grad) < 1e-3


def test_qwen3_next_reference_in_blocks_equals_itself_whole(monkeypatch):
    """The recurrence in blocks of tokens that do not divide the sequence,
    attention in blocks of queries: the same loss and gradients."""
    params = common.unflatten(qwen3_next.seeded_weights(QWEN, 3))
    (ids,) = _qwen_batch(1, seq=96)
    whole, whole_grad = jax.value_and_grad(qwen3_next.loss_fn(QWEN))(params,
                                                                     ids)
    monkeypatch.setattr(qwen3_next, "TOKEN_BLOCK", 20)   # 96 = 4 * 20 + 16
    monkeypatch.setattr(qwen3_next, "QUERY_BLOCK", 32)
    blocks, blocks_grad = jax.value_and_grad(qwen3_next.loss_fn(QWEN))(
        params, ids)
    assert abs(float(whole) - float(blocks)) < 1e-5
    assert _worst_relative_difference(blocks_grad, whole_grad) < 1e-4


def test_qwen3_next_seeded_weights_give_every_leaf_a_first_gradient():
    """PR 23's ResNet lesson: a leaf whose first gradient is exactly zero in
    the program and the reference alike is held to nothing."""
    params = common.unflatten(qwen3_next.seeded_weights(QWEN, 9))
    (ids,) = _qwen_batch(2)
    grads = jax.grad(qwen3_next.loss_fn(QWEN))(params, ids)
    norms = {k: float(v) for k, v in
             common.leaf_norms(common.flatten(grads)).items()}
    assert len(norms) == len(qwen3_next.param_shapes(QWEN))
    assert min(norms.values()) > 0.0, min(norms, key=norms.get)
    flat = qwen3_next.seeded_weights(QWEN, 9)
    a_log = np.asarray(flat["layers_0/linear_attn/A_log"])
    assert np.all(np.exp(a_log) > 0) and np.all(np.exp(a_log) < 16)
    assert np.all(np.asarray(flat["layers_0/linear_attn/norm"]) == 1)
    assert np.all(np.asarray(flat["norm/weight"]) == 0)


def test_qwen3_next_gate_weights_are_the_normalised_top_k():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(50, 16)),
                    jnp.float32)
    router = jnp.asarray(np.random.default_rng(1).normal(size=(16, 12)),
                         jnp.float32)
    gates = np.asarray(qwen3_next.gate_weights(x, router, 3))
    assert np.all((gates > 0).sum(-1) == 3)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    for t in range(50):
        top = np.argsort(-probs[t])[:3]
        assert set(np.nonzero(gates[t])[0]) == set(top)
        np.testing.assert_allclose(gates[t, top],
                                   probs[t, top] / probs[t, top].sum(),
                                   rtol=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_qwen3_next(seed):
    numbers = _control_numbers(
        lambda p: qwen3_next.loss_fn(QWEN, p),
        qwen3_next.seeded_weights(QWEN, seed),
        [_qwen_batch(seed * 10 + i) for i in range(3)], "adam", 1e-4, 2)
    correct, lines = check.verdict(numbers, {
        k: qwen3_next_80b_a3b.LIMITS[k] for k in numbers})
    assert not correct, lines
