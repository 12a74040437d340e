"""End-to-end DP training on the virtual mesh: loss decreases and matches a
single-device reference — the framework's minimum end-to-end slice
(SURVEY §7.2 step 2, reference examples/tensorflow2_mnist.py analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models.mlp import MLP, ConvNet
from horovod_tpu.training import init_train_state, make_train_step, shard_batch


def _make_problem(rng, n=64, d=16, classes=10):
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return x, y


def test_mlp_training_loss_decreases(hvd_init, rng):
    x, y = _make_problem(rng)
    model = MLP(features=(32, 10))
    opt = optax.sgd(0.1)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    step = make_train_step(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=loss_fn,
        optimizer=opt,
    )
    state = init_train_state(model, opt, jnp.zeros((2, 16)))
    xs, ys = shard_batch(x), shard_batch(y)

    losses = []
    for _ in range(60):
        state, loss = step(state, xs, ys)
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0] * 0.6, losses


def test_dp_equals_single_device_sgd(hvd_init, rng):
    """The core DP invariant: allreduced-mean-gradient SGD over 8 shards ==
    full-batch SGD on one device (reference's correctness contract for
    DistributedOptimizer)."""
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(8, 10))
    opt = optax.sgd(0.5)

    def loss_fn(logits, labels):
        # sum-then-divide by global batch => shard means weighted equally
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    step = make_train_step(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=loss_fn, optimizer=opt,
    )
    state = init_train_state(model, opt, jnp.zeros((2, 16)))
    params0 = jax.device_get(state.params)

    xs, ys = shard_batch(x), shard_batch(y)
    state, _ = step(state, xs, ys)
    dp_params = jax.device_get(state.params)

    # single-device full-batch reference (numpy-exact via jax on cpu mesh's
    # first device through jit to keep precision comparable)
    @jax.jit
    def ref_step(p):
        def full_loss(p):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        g = jax.grad(full_loss)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.5 * b, p, g)

    with jax.default_device(jax.devices("cpu")[0]):
        ref = jax.device_get(ref_step(params0))

    flat_dp = jax.tree_util.tree_leaves(dp_params)
    flat_ref = jax.tree_util.tree_leaves(ref)
    for a, b in zip(flat_dp, flat_ref):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_convnet_with_batch_stats(hvd_init, rng):
    from horovod_tpu.models.resnet import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.float32)
    opt = optax.sgd(0.01)
    x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=(16,)).astype(np.int32)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    step = make_train_step(
        apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
        has_batch_stats=True,
    )
    state = init_train_state(
        model, opt, jnp.zeros((2, 16, 16, 3)), has_batch_stats=True
    )
    state, loss1 = step(state, shard_batch(x), shard_batch(y))
    state, loss2 = step(state, shard_batch(x), shard_batch(y))
    assert np.isfinite(float(jax.device_get(loss2)))
    assert "batch_stats" in state.model_state


def test_bert_tiny_forward(hvd_init, rng):
    from horovod_tpu.models.bert import bert_tiny

    model = bert_tiny(dtype=jnp.float32)
    ids = rng.integers(0, 1024, size=(2, 32)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    out = model.apply(variables, ids)
    assert out.shape == (2, 32, 128)
    assert np.isfinite(np.asarray(out)).all()


def test_in_graph_steps_matches_sequential(hvd_init, rng):
    """K scanned in-graph steps on one batch == K sequential step() calls
    (the synthetic-benchmark mode)."""
    x, y = _make_problem(rng)
    model = MLP(features=(32, 10))
    opt = optax.sgd(0.1)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    mk = dict(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=loss_fn, optimizer=opt, donate=False,
    )
    step1 = make_train_step(**mk)
    step4 = make_train_step(**mk, in_graph_steps=4)
    state_a = init_train_state(model, opt, jnp.zeros((2, 16)))
    state_b = init_train_state(model, opt, jnp.zeros((2, 16)))
    xs, ys = shard_batch(x), shard_batch(y)

    for _ in range(4):
        state_a, loss_a = step1(state_a, xs, ys)
    state_b, loss_b = step4(state_b, xs, ys)

    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
    for pa, pb in zip(jax.tree_util.tree_leaves(state_a.params),
                      jax.tree_util.tree_leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-5, atol=1e-6)
    assert int(state_b.step) == 4


def test_space_to_depth_stem_equivalent(rng):
    """The s2d stem (MLPerf TPU trick) shares the (7,7,C,F) kernel param
    and produces the plain conv stem's exact output."""
    import jax

    from horovod_tpu.models.resnet import ResNet18

    with jax.default_device(jax.devices("cpu")[0]):
        m1 = ResNet18(num_classes=10, dtype=jnp.float32)
        m2 = ResNet18(num_classes=10, dtype=jnp.float32,
                      stem="space_to_depth")
        x = jnp.asarray(rng.normal(size=(2, 64, 64, 3)).astype(np.float32))
        v = m1.init(jax.random.PRNGKey(0), x, train=False)
        o1 = m1.apply(v, x, train=False)
        o2 = m2.apply(v, x, train=False)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-4)


def test_orbax_checkpoint_roundtrip(hvd_init, rng, tmp_path):
    """save/restore/latest_step through orbax, with the broadcast-on-
    restore resume contract (reference: rank-0 writes +
    broadcast_parameters on start)."""
    pytest.importorskip("orbax.checkpoint")
    from horovod_tpu.utils.checkpoint import (
        latest_step, restore_checkpoint, save_checkpoint,
    )

    state = {
        "w": rng.normal(size=(4, 4)).astype(np.float32),
        "step": np.asarray(7, np.int32),
    }
    base = str(tmp_path / "ckpt")
    out = save_checkpoint(base, state, step=7)
    assert out is not None and out.endswith("step_7")
    save_checkpoint(base, {**state, "step": np.asarray(9, np.int32)},
                    step=9)
    assert latest_step(base) == 9

    like = {"w": np.zeros((4, 4), np.float32),
            "step": np.asarray(0, np.int32)}
    restored = restore_checkpoint(base, like)      # latest: step 9
    assert int(restored["step"]) == 9
    np.testing.assert_allclose(np.asarray(restored["w"]), state["w"])
    restored7 = restore_checkpoint(base, like, step=7)
    assert int(restored7["step"]) == 7
