"""The ResNet family on the one path it has (XLA convolutions, flax
BatchNorm, relu in place): published sizes, the tree the benchmark's
reference lays its weights over, what the blocks start at, and a toy-width
model through the train step."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models.resnet import (
    MODELS, BasicBlock, BottleneckBlock, ResNet,
)
from horovod_tpu.training import init_train_state, make_train_step, shard_batch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS = {"basic": BasicBlock, "bottleneck": BottleneckBlock}


def _toy(block, **kw):
    return ResNet(stage_sizes=[1, 1], block_cls=BLOCKS[block],
                  num_classes=10, num_filters=8, dtype=jnp.float32, **kw)


def _leaf_names(tree):
    return {"/".join(k.key for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# He et al. 2015, table 1, as torchvision and Keras count them: trainable
# parameters in millions at 1000 classes
@pytest.mark.parametrize("depth, millions", [
    (18, 11.69), (34, 21.80), (50, 25.56), (101, 44.55), (152, 60.19)])
def test_resnet_family_parameter_counts(depth, millions):
    model = MODELS[f"ResNet{depth}"](num_classes=1000)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3)), train=False))
    count = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes["params"]))
    assert round(count / 1e6, 2) == millions
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(shapes))


def test_resnet50_tree_is_the_benchmark_references_tree():
    """``benchmarks/references/resnet50.py`` lays its seeded weights over
    the program's parameters by leaf name and refuses a tree that
    differs: the 161 names and shapes are the model's interface to the
    ``resnet50-b256`` cell."""
    from benchmarks.references import resnet50

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "resnet50.json")) as fh:
        cfg = json.load(fh)
    model = ResNet(stage_sizes=cfg["stage_sizes"], block_cls=BottleneckBlock,
                   num_classes=cfg["num_classes"],
                   num_filters=cfg["num_filters"])
    size = cfg["image_size"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, size, size, 3))))
    got = {k: tuple(v.shape)
           for k, v in _leaf_names(shapes["params"]).items()}
    want = resnet50.param_shapes(cfg)
    assert len(want) == 161
    assert got == {k: tuple(v) for k, v in want.items()}
    # one running mean and variance for each of the 53 BatchNorms
    assert len(jax.tree_util.tree_leaves(shapes["batch_stats"])) == 2 * 53


@pytest.mark.parametrize("block", list(BLOCKS))
def test_last_batchnorm_scale_starts_at_zero(block):
    """Goyal et al. 2017: each block starts as the identity, its last
    BatchNorm's scale at zero and every other scale at one."""
    with jax.default_device(jax.devices("cpu")[0]):
        params = _toy(block).init(jax.random.PRNGKey(0),
                                  jnp.zeros((2, 16, 16, 3)))["params"]
    last = {"basic": "BatchNorm_1", "bottleneck": "BatchNorm_2"}[block]
    scales = {k: np.asarray(v) for k, v in _leaf_names(params).items()
              if k.endswith("/scale")}
    in_blocks = [k for k in scales if "Block_" in k]
    assert len([k for k in in_blocks if f"/{last}/" in k]) == 2
    for name in in_blocks:
        want = 0.0 if f"/{last}/" in name else 1.0
        np.testing.assert_array_equal(scales[name], want, err_msg=name)
    np.testing.assert_array_equal(scales["bn_init/scale"], 1.0)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_resnet_trains_through_the_step(hvd_init, rng, block):
    """Toy width through ``make_train_step`` with batch statistics: the
    loss is finite and falls, every parameter leaf and every running
    statistic moves."""
    model = _toy(block)
    opt = optax.sgd(0.05, momentum=0.9)
    x = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=(16,)).astype(np.int32)
    step = make_train_step(
        apply_fn=model.apply,
        loss_fn=lambda logits, labels:
        optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(),
        optimizer=opt, has_batch_stats=True, donate=False)
    state0 = init_train_state(model, opt, jnp.zeros((2, 16, 16, 3)),
                              has_batch_stats=True)
    state, xs, ys = state0, shard_batch(x), shard_batch(y)
    losses = []
    for _ in range(8):
        state, loss = step(state, xs, ys)
        losses.append(float(jax.device_get(loss)))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.9 * losses[0], losses
    for tree0, tree in ((state0.params, state.params),
                        (state0.model_state, state.model_state)):
        before, after = _leaf_names(tree0), _leaf_names(tree)
        assert before.keys() == after.keys()
        for name in before:
            assert not np.array_equal(np.asarray(before[name]),
                                      np.asarray(after[name])), name


def test_eval_mode_reads_running_statistics(rng):
    """``train=False`` normalises with the stored mean and variance and
    writes nothing: other statistics, other logits; ``train=True`` on the
    same input ignores them."""
    with jax.default_device(jax.devices("cpu")[0]):
        model = _toy("basic")
        x = jnp.asarray(rng.normal(size=(4, 16, 16, 3)).astype(np.float32))
        v = model.init(jax.random.PRNGKey(0), x)
        # past the zero-initialised last scales, or no block would show
        v = {"params": jax.tree_util.tree_map(lambda p: p + 0.5,
                                              v["params"]),
             "batch_stats": v["batch_stats"]}
        shifted = {"params": v["params"],
                   "batch_stats": jax.tree_util.tree_map(
                       lambda s: s + 1.0, v["batch_stats"])}
        with pytest.raises(Exception, match="batch_stats"):
            model.apply(v, x, train=True)   # would write, and may not
        out = model.apply(v, x, train=False)
        out_shifted = model.apply(shifted, x, train=False)
        assert not np.allclose(np.asarray(out), np.asarray(out_shifted))
        trained, _ = model.apply(v, x, train=True, mutable=["batch_stats"])
        trained_shifted, _ = model.apply(shifted, x, train=True,
                                         mutable=["batch_stats"])
        np.testing.assert_array_equal(np.asarray(trained),
                                      np.asarray(trained_shifted))


def test_unknown_stem_is_rejected():
    with pytest.raises(ValueError, match="unknown stem 'pallas'"):
        jax.eval_shape(
            lambda: _toy("basic", stem="pallas").init(
                jax.random.PRNGKey(0), jnp.zeros((2, 16, 16, 3))))
