"""End-to-end DP training on the virtual mesh: loss decreases and matches a
single-device reference — the framework's minimum end-to-end slice
(SURVEY §7.2 step 2, reference examples/tensorflow2_mnist.py analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flax import linen as nn

import horovod_tpu as hvd
from horovod_tpu.models.mlp import MLP, ConvNet
from horovod_tpu.training import init_train_state, make_train_step, shard_batch


def _make_problem(rng, n=64, d=16, classes=10):
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return x, y


def _xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


#: the optimizers the benchmark's cells run (SGD with momentum, Adam) and
#: their neighbours; a new instance per call, optax transformations hold
#: no state but the test must not depend on that
OPTIMIZERS = {
    "sgd": lambda: optax.sgd(0.1),
    "momentum": lambda: optax.sgd(0.1, momentum=0.9),
    "adam": lambda: optax.adam(1e-2),
    "adamw": lambda: optax.adamw(1e-2, weight_decay=0.01),
}
optimizers = pytest.mark.parametrize("optimizer", list(OPTIMIZERS))


@pytest.fixture()
def world(request, cpu_devices):
    """A world of ``request.param`` ranks in place of ``hvd_init``'s 8."""
    hvd.shutdown()
    hvd.init(devices=cpu_devices[:request.param])
    yield request.param
    hvd.shutdown()


def _mlp_step(model, opt, **kw):
    return make_train_step(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=_xent, optimizer=opt, **kw)


def _drive(model, opt, x, y, steps, **kw):
    """``steps`` calls of the built step from a fresh state: ``(initial
    params on the host, final state, [loss])``."""
    step = _mlp_step(model, opt, **kw)
    state = init_train_state(model, opt, jnp.zeros((2,) + x.shape[1:]))
    params0 = jax.device_get(state.params)
    xs, ys = shard_batch(x), shard_batch(y)
    losses = []
    for _ in range(steps):
        state, loss = step(state, xs, ys)
        losses.append(float(jax.device_get(loss)))
    return params0, state, losses


def _plain_reference(model, opt, params, x, y, steps):
    """The same steps with no framework: ``jax.grad`` of the whole batch's
    loss and optax, on one device.  ``(params, opt_state, [loss])``."""
    @jax.jit
    def ref_step(p, s):
        loss, g = jax.value_and_grad(
            lambda p: _xent(model.apply({"params": p}, x), y))(p)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    losses = []
    with jax.default_device(jax.devices("cpu")[0]):
        opt_state = opt.init(params)
        for _ in range(steps):
            params, opt_state, loss = ref_step(params, opt_state)
            losses.append(float(loss))
    return jax.device_get(params), jax.device_get(opt_state), losses


def _assert_trees_close(got, want, rtol=2e-4, atol=2e-5):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


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


@optimizers
def test_in_graph_steps_matches_sequential(hvd_init, rng, optimizer):
    """K scanned in-graph steps on one batch == K sequential step() calls
    (the synthetic-benchmark mode), whatever state the optimizer carries
    through the scan."""
    x, y = _make_problem(rng)
    model = MLP(features=(32, 10))
    opt = OPTIMIZERS[optimizer]()

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


@optimizers
@pytest.mark.parametrize("world", [1, 2, 4, 8], indirect=True)
def test_step_matches_a_plain_reference(world, rng, optimizer):
    """The contract of the function every cell enters by: three steps
    through ``make_train_step`` over ``world`` ranks give the losses and
    every parameter leaf that ``jax.grad`` + optax give on one device for
    the whole batch."""
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(8, 10))
    opt = OPTIMIZERS[optimizer]()
    params0, state, losses = _drive(model, opt, x, y, 3)
    ref_params, _, ref_losses = _plain_reference(model, opt, params0, x, y, 3)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    _assert_trees_close(jax.device_get(state.params), ref_params)
    assert int(state.step) == 3


@optimizers
def test_donated_and_undonated_steps_agree(hvd_init, rng, optimizer):
    """``donate=True`` (what the cells run) walks the trajectory of
    ``donate=False``: an update never reads a buffer it has given away."""
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(8, 10))
    _, donated, l_don = _drive(model, OPTIMIZERS[optimizer](), x, y, 3,
                               donate=True)
    _, kept, l_kept = _drive(model, OPTIMIZERS[optimizer](), x, y, 3,
                             donate=False)
    assert l_don == l_kept
    for a, b in zip(jax.tree_util.tree_leaves((donated.params,
                                               donated.opt_state)),
                    jax.tree_util.tree_leaves((kept.params,
                                               kept.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class _MixedMLP(nn.Module):
    """A bfloat16 layer under a float32 one: what a model with reduced
    precision embeddings or experts hands the step."""

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(16, param_dtype=jnp.bfloat16)(x)
        return nn.Dense(10)(nn.relu(x))


@optimizers
def test_mixed_dtype_params_keep_their_dtypes_and_match(hvd_init, rng,
                                                        optimizer):
    """Every leaf of params and optimizer state leaves the step in the
    dtype it came in (the buckets pack by dtype and unpack to it), and
    the trajectory is the plain reference's to bfloat16's resolution."""
    x, y = _make_problem(rng, n=32)
    model = _MixedMLP()
    opt = OPTIMIZERS[optimizer]()
    step = _mlp_step(model, opt, donate=False)
    state0 = init_train_state(model, opt, jnp.zeros((2, 16)))
    dtypes0 = [l.dtype for l in jax.tree_util.tree_leaves(state0)]
    assert {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)} <= set(
        l.dtype for l in jax.tree_util.tree_leaves(state0.params))
    state, xs, ys = state0, shard_batch(x), shard_batch(y)
    for _ in range(2):
        state, loss = step(state, xs, ys)
    assert [l.dtype for l in jax.tree_util.tree_leaves(state)] == dtypes0
    ref_params, _, ref_losses = _plain_reference(
        model, opt, jax.device_get(state0.params), x, y, 2)
    np.testing.assert_allclose(float(loss), ref_losses[-1], rtol=2e-2)
    _assert_trees_close(jax.device_get(state.params), ref_params,
                        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("threshold", [1, "default", 1 << 30],
                         ids=["tiny", "default", "one bucket"])
def test_threshold_bytes_does_not_change_the_result(hvd_init, rng,
                                                    threshold):
    """How the gradients are packed into buckets (one a leaf, the
    default's, all in one) is a schedule, not arithmetic: the parameters
    are those of the step built with no threshold named."""
    from horovod_tpu.utils import env as env_util

    if threshold == "default":
        threshold = env_util.fusion_threshold_bytes()
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(32, 10))
    _, unnamed, l_unnamed = _drive(model, OPTIMIZERS["adam"](), x, y, 2)
    _, named, l_named = _drive(model, OPTIMIZERS["adam"](), x, y, 2,
                               threshold_bytes=threshold)
    np.testing.assert_allclose(l_named, l_unnamed, rtol=1e-6)
    _assert_trees_close(jax.device_get(named.params),
                        jax.device_get(unnamed.params), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("world", [2, 8], indirect=True)
def test_reported_loss_is_the_mean_over_ranks(world, rng):
    """The loss a step returns is the mean of the ranks' own losses
    (MetricAverageCallback's meaning), each over its shard of the batch."""
    x, y = _make_problem(rng, n=32)
    # rows that are easy for some ranks and hard for others
    x[: 32 // world] *= 4.0
    model = MLP(features=(8, 10))
    params0, _, losses = _drive(model, OPTIMIZERS["sgd"](), x, y, 1)
    per_rank = [
        float(_xent(model.apply({"params": params0}, xr), yr))
        for xr, yr in zip(np.split(x, world), np.split(y, world))]
    assert max(per_rank) - min(per_rank) > 1e-3
    np.testing.assert_allclose(losses[0], np.mean(per_rank), rtol=1e-5)


def test_op_sum_and_average_differ_by_the_world_size(hvd_init, rng):
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(8, 10))
    p0, averaged, _ = _drive(model, optax.sgd(0.01), x, y, 1)
    _, summed, _ = _drive(model, optax.sgd(0.01), x, y, 1, op=hvd.Sum)
    moved = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - b, jax.device_get(averaged.params), p0)
    moved_sum = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - b, jax.device_get(summed.params), p0)
    _assert_trees_close(
        moved_sum, jax.tree_util.tree_map(lambda d: hvd.size() * d, moved),
        rtol=1e-3, atol=1e-6)


@pytest.mark.parametrize("path", ["hierarchical", "two_level"])
def test_hierarchical_and_two_level_equal_flat(hvd_init, rng, path):
    """Both per-leaf reductions over the 2 x 4 world (reduce inside a
    node, across nodes, back) are the flat all-reduce's numbers."""
    x, y = _make_problem(rng, n=32)
    model = MLP(features=(8, 10))
    _, flat, l_flat = _drive(model, OPTIMIZERS["momentum"](), x, y, 2)
    _, split, l_split = _drive(model, OPTIMIZERS["momentum"](), x, y, 2,
                               **{path: True})
    np.testing.assert_allclose(l_split, l_flat, rtol=1e-5)
    _assert_trees_close(jax.device_get(split.params),
                        jax.device_get(flat.params), rtol=1e-4, atol=1e-6)


def _tiny_resnet():
    from horovod_tpu.models.resnet import BasicBlock, ResNet

    return ResNet(stage_sizes=[1, 1], block_cls=BasicBlock, num_classes=10,
                  num_filters=8, dtype=jnp.float32)


def _tiny_gpt():
    from horovod_tpu.models.gpt import gpt_tiny

    return gpt_tiny(vocab_size=64, hidden_dim=32, num_layers=1, num_heads=2,
                    mlp_dim=64, max_len=16, dtype=jnp.float32)


@pytest.mark.parametrize("build, sample, batch_stats", [
    (lambda: MLP(features=(8, 10)), jnp.zeros((2, 16)), False),
    (_tiny_resnet, jnp.zeros((2, 16, 16, 3)), True),
    (_tiny_gpt, jnp.zeros((2, 16), jnp.int32), False),
], ids=["mlp", "convnet+batch_stats", "gpt-tiny"])
def test_init_train_state_replicates_every_leaf(hvd_init, build, sample,
                                                batch_stats):
    """Before step 1 every leaf of the state — parameters, optimizer
    moments, batch statistics, the step counter — is whole on each of the
    eight devices, and the same on each."""
    model, opt = build(), optax.adam(1e-3)
    state = init_train_state(model, opt, sample,
                             has_batch_stats=batch_stats)
    assert ("batch_stats" in state.model_state) == batch_stats
    leaves = jax.tree_util.tree_leaves(state)
    assert len(leaves) > 3 * len(jax.tree_util.tree_leaves(state.params))
    for leaf in leaves:
        assert leaf.sharding.is_fully_replicated
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == hvd.size()
        for s in shards[1:]:
            assert s.data.shape == leaf.shape
            np.testing.assert_array_equal(np.asarray(s.data),
                                          np.asarray(shards[0].data))


@pytest.mark.parametrize("keyword", ["fused_optimizer", "remat_policy"])
def test_make_train_step_rejects(hvd_init, keyword):
    """PR 12's compute knobs are gone, not ignored: the update is optax's,
    and what the backward pass recomputes is the model's to say
    (``nn.remat``)."""
    model = MLP(features=(8, 10))
    with pytest.raises(TypeError, match=keyword):
        _mlp_step(model, optax.sgd(0.1), **{keyword: None})


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
