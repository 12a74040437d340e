"""Each plain reference against the program's model at toy size, the plain
optimizers against optax, and the control: the reference in float8, put in
the program's place, has to come out as not correct under the limits the
configurations ship."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import benchmark_tiny
from benchmarks.configs import gpt2_small, resnet50 as resnet50_config
from benchmarks.harness import check
from benchmarks.references import common, gpt2, resnet50

GPT = benchmark_tiny.GPT_TINY
RESNET = dict(benchmark_tiny.RESNET_TINY, stage_sizes=[1, 2, 1, 1])


def _worst_relative_difference(a, b):
    diff = common.leaf_diff_norms(common.flatten(a), common.flatten(b))
    norm = common.leaf_norms(common.flatten(b))
    scale = 1e-3 * max(float(v) for v in norm.values())
    return max(float(diff[k]) / max(float(norm[k]), scale) for k in diff)


def _gpt_batch(seed, rows=2, seq=128):
    return (np.random.default_rng(seed).integers(
        0, GPT["vocab_size"], (rows, seq)).astype(np.int32),)


def _resnet_batch(seed, rows=16, size=32):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (rows, size, size, 3), dtype=np.uint8),
            rng.integers(0, RESNET["num_classes"], (rows,)).astype(np.int32))


def test_gpt2_reference_matches_the_programs_model():
    from horovod_tpu.models.gpt import GPT as Model, next_token_loss

    params = common.unflatten(gpt2.seeded_weights(GPT, 128, 7))
    model = Model(vocab_size=GPT["vocab_size"], hidden_dim=GPT["n_embd"],
                  num_layers=GPT["n_layer"], num_heads=GPT["n_head"],
                  mlp_dim=GPT["n_inner"], max_len=128, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 128), jnp.int32))["params"]
    assert {k: v.shape for k, v in common.flatten(shapes).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()}
    (ids,) = _gpt_batch(0)
    want, want_grad = jax.value_and_grad(gpt2.loss_fn(GPT))(params, ids)
    got, got_grad = jax.value_and_grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert _worst_relative_difference(got_grad, want_grad) < 1e-3


def test_gpt2_reference_in_blocks_equals_itself_whole(monkeypatch):
    params = common.unflatten(gpt2.seeded_weights(GPT, 128, 3))
    (ids,) = _gpt_batch(1)
    whole, whole_grad = jax.value_and_grad(gpt2.loss_fn(GPT))(params, ids)
    monkeypatch.setattr(gpt2, "QUERY_BLOCK", 32)
    monkeypatch.setattr(gpt2, "TOKEN_BLOCK", 100)   # does not divide 254
    blocks, blocks_grad = jax.value_and_grad(gpt2.loss_fn(GPT))(params, ids)
    assert abs(float(whole) - float(blocks)) < 1e-5
    assert _worst_relative_difference(blocks_grad, whole_grad) < 1e-4


def test_resnet50_reference_matches_the_programs_model():
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    # the seeded weights leave every residual branch open, so every
    # convolution's gradient is compared
    params = common.unflatten(resnet50.seeded_weights(RESNET, 5))
    model = ResNet(stage_sizes=RESNET["stage_sizes"],
                   block_cls=BottleneckBlock,
                   num_classes=RESNET["num_classes"],
                   num_filters=RESNET["num_filters"], dtype=jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.zeros((2, 32, 32, 3)))
    assert {k: v.shape for k, v in
            common.flatten(variables["params"]).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()}
    stats = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), variables["batch_stats"])
    images, labels = _resnet_batch(0)

    def program_loss(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": stats},
            images.astype(jnp.float32) / 255.0, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    want, want_grad = jax.value_and_grad(resnet50.loss_fn(RESNET))(
        params, images, labels)
    got, got_grad = jax.value_and_grad(program_loss)(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert _worst_relative_difference(got_grad, want_grad) < 1e-3


def test_resnet50_seeded_weights_give_every_leaf_a_first_gradient():
    """A block that started as the identity would leave the first gradient
    of its three convolutions exactly zero in the program and the
    reference alike, and ``correct`` would compare 0 with 0."""
    params = common.unflatten(resnet50.seeded_weights(RESNET, 9))
    images, labels = _resnet_batch(2)
    grads = jax.grad(resnet50.loss_fn(RESNET))(params, images, labels)
    norms = {k: float(v) for k, v in
             common.leaf_norms(common.flatten(grads)).items()}
    assert len(norms) == len(resnet50.param_shapes(RESNET))
    assert min(norms.values()) > 0.0, min(norms, key=norms.get)


@pytest.mark.parametrize("name,make", [
    ("adam", lambda: optax.adam(1e-2)),
    ("sgd_momentum", lambda: optax.sgd(1e-2, momentum=0.9)),
])
def test_plain_optimizers_match_optax(name, make):
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": {"c": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}}
    init, update = common.OPTIMIZERS[name]
    opt = make()
    mine, state = params, init(params)
    theirs, opt_state = params, opt.init(params)
    for i in range(3):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
            params)
        mine, state = update(mine, grads, state, lr=1e-2)
        updates, opt_state = opt.update(grads, opt_state, theirs)
        theirs = optax.apply_updates(theirs, updates)
    assert _worst_relative_difference(mine, theirs) < 1e-5


def test_train_steps_averages_blocks_of_rows_like_data_parallel_chips():
    params = common.unflatten(gpt2.seeded_weights(GPT, 128, 11))
    batches = [_gpt_batch(s, rows=4) for s in range(3)]
    kw = dict(optimizer="adam", lr=1e-4)
    whole = common.train_steps(gpt2.loss_fn(GPT), params, batches,
                               rows_per_block=4, **kw)
    blocks = common.train_steps(gpt2.loss_fn(GPT), params, batches,
                                rows_per_block=1, **kw)
    assert np.allclose(whole["losses"], blocks["losses"], rtol=1e-5)
    gap, _ = check.worst_leaf_gap(blocks["grad_norms"], whole["grad_norms"])
    assert gap < 1e-4
    with pytest.raises(ValueError, match="do not split"):
        common.train_steps(gpt2.loss_fn(GPT), params, batches,
                           rows_per_block=3, **kw)


def _control_numbers(loss_fn, weights, batches, optimizer, lr, rows):
    ref = {"init": lambda seed: weights, "loss": loss_fn,
           "optimizer": optimizer, "lr": lr}
    return check.first_steps_numbers(
        common.follow(ref, 0, batches, rows, "fp8"),
        common.follow(ref, 0, batches, rows))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_gpt2(seed):
    numbers = _control_numbers(
        lambda p: gpt2.loss_fn(GPT, p), gpt2.seeded_weights(GPT, 128, seed),
        [_gpt_batch(seed * 10 + i) for i in range(3)], "adam", 1e-4, 2)
    correct, lines = check.verdict(numbers, {
        k: gpt2_small.LIMITS[k] for k in numbers})
    assert not correct, lines


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct_resnet50(seed):
    numbers = _control_numbers(
        lambda p: resnet50.loss_fn(RESNET, p),
        resnet50.seeded_weights(RESNET, seed),
        [_resnet_batch(seed * 10 + i) for i in range(3)], "sgd_momentum",
        0.01, 16)
    correct, lines = check.verdict(numbers, {
        k: resnet50_config.LIMITS[k] for k in numbers})
    assert not correct, lines


def test_operand_rounding_is_float8_and_passes_gradients_straight():
    q = common.operand_rounding("fp8")
    x = jnp.asarray([1.0, 0.53, -0.07, 0.0009], jnp.float32)
    rounded = q(x)
    # 3 mantissa bits: relative steps of 1/8 at most, the largest kept
    assert float(rounded[0]) == 1.0
    assert 0 < abs(float(rounded[1]) - 0.53) <= 0.53 / 16
    assert np.allclose(jax.grad(lambda v: jnp.sum(q(v) * 2.0))(x), 2.0)
    assert common.operand_rounding("float32")(x) is x
    with pytest.raises(ValueError, match="unknown precision"):
        common.operand_rounding("int4")


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    gap, where = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "tiny": 2e-9},
                                      ref)
    assert where == "a" and abs(gap - 0.1) < 1e-12   # tiny: 1e-9 / median 1
    gap, where = check.worst_leaf_gap({"a": 1.0, "b": 2.0, "tiny": 0.5}, ref)
    assert where == "tiny" and abs(gap - 0.5) < 1e-6
    # zero in both is agreement; zero in the reference alone is not
    assert check.worst_leaf_gap({"a": 0.0}, {"a": 0.0}) == (0.0, "")
    assert check.worst_leaf_gap({"a": 1e-3}, {"a": 0.0})[0] == float("inf")
    assert check.worst_leaf_gap({"a": float("nan")}, {"a": 1.0})[0] \
        == float("inf")


def test_sketch_differences_estimate_the_norm_of_the_difference():
    rng = np.random.default_rng(0)
    a = {"w": jnp.asarray(rng.normal(size=(300, 200)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(50,)), jnp.float32)}
    noise = {k: 0.01 * jnp.asarray(rng.normal(size=v.shape), jnp.float32)
             for k, v in a.items()}
    b = {k: a[k] + noise[k] for k in a}
    sa, sb = common.leaf_sketches(a), common.leaf_sketches(b)
    assert sa["w"].shape == (common.SKETCHES,)
    # same signs for every caller: a sketch is linear in its leaf
    assert np.allclose(np.asarray(sb["w"]) - np.asarray(sa["w"]),
                       np.asarray(common.leaf_sketches(noise)["w"]),
                       atol=1e-3)
    norms = {k: float(v) for k, v in common.leaf_norms(a).items()}
    to_lists = lambda s: {k: [float(x) for x in v]  # noqa: E731
                          for k, v in s.items()}
    gap = check.sketch_gap(to_lists(sb), to_lists(sa), norms, list(norms))
    # 1% noise on unit-variance leaves: about 0.01, whatever the leaf's size,
    # where the gap between the two norms is of the order of 1e-4
    assert 0.003 < gap < 0.03
    assert check.worst_leaf_gap(
        {k: float(v) for k, v in common.leaf_norms(b).items()}, norms)[0] \
        < gap / 5
    assert check.sketch_gap(to_lists(sa), to_lists(sa), norms,
                            list(norms)) == 0.0


# -- the walk holds no copy of the parameters that it does not read (PR 40) --

def _mlp_params(seed=0, d=24, h=40):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return {"a": {"w": 0.3 * draw(d, h), "b": jnp.zeros((h,), jnp.float32)},
            "c": {"w": 0.3 * draw(h, 1)}}


def _mlp_loss(params, x, y):
    hidden = jnp.tanh(x @ params["a"]["w"] + params["a"]["b"])
    return jnp.mean(jnp.square((hidden @ params["c"]["w"])[:, 0] - y))


def _mlp_batches(steps=3, rows=4, d=24):
    rng = np.random.default_rng(7)
    return [(rng.normal(size=(rows, d)).astype(np.float32),
             rng.normal(size=(rows,)).astype(np.float32))
            for _ in range(steps)]


def _plain_walk(loss_fn, params, batches, *, optimizer, lr, rows_per_block):
    """``train_steps`` as it was before it gave anything away: every
    update's inputs and outputs side by side, the blocks' sum and their
    mean two trees, the start weights held through the steps."""
    init, update = common.OPTIMIZERS[optimizer]
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    step_fn = jax.jit(lambda p, g, s: update(p, g, s, lr=lr))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    start, state = params, init(params)
    losses, first = [], None
    for arrays in batches:
        blocks = arrays[0].shape[0] // rows_per_block
        loss_sum, grad_sum = 0.0, None
        for i in range(blocks):
            part = tuple(jnp.asarray(a[i * rows_per_block:
                                       (i + 1) * rows_per_block])
                         for a in arrays)
            loss, grads = grad_fn(params, *part)
            loss_sum = loss_sum + loss
            grad_sum = grads if grad_sum is None else add(grad_sum, grads)
        grads = jax.tree_util.tree_map(lambda g: g / blocks, grad_sum)
        if first is None:
            first = (common.leaf_norms(common.flatten(grads)),
                     common.leaf_sketches(common.flatten(grads)))
        params, state = step_fn(params, grads, state)
        losses.append(float(loss_sum) / blocks)
    moved = common.leaf_diff_norms(common.flatten(params),
                                   common.flatten(start))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in first[0].items()},
            "grad_sketches": {k: [float(x) for x in np.asarray(v)]
                              for k, v in first[1].items()},
            "update_norms": {k: float(v) for k, v in moved.items()}}


@pytest.mark.parametrize("rows_per_block", [4, 2, 1])
@pytest.mark.parametrize("optimizer", ["adam", "sgd_momentum"])
def test_train_steps_returns_what_a_plain_walk_returns(optimizer,
                                                       rows_per_block):
    """Giving buffers away changes where a result is written, not what it
    is: equality, no tolerance — from a tree and from a function that
    makes the tree."""
    kw = dict(optimizer=optimizer, lr=1e-2, rows_per_block=rows_per_block)
    want = _plain_walk(_mlp_loss, _mlp_params(), _mlp_batches(), **kw)
    assert common.train_steps(_mlp_loss, _mlp_params(), _mlp_batches(),
                              **kw) == want
    assert common.train_steps(_mlp_loss, _mlp_params, _mlp_batches(),
                              **kw) == want
    assert len(want["losses"]) == 3 and min(
        want["update_norms"].values()) > 0


@pytest.mark.parametrize("optimizer", ["adam", "sgd_momentum"])
def test_the_callers_parameters_survive_the_walk(optimizer):
    params = _mlp_params(3)
    before = jax.tree_util.tree_map(np.array, params)
    kw = dict(optimizer=optimizer, lr=1e-2, rows_per_block=2)
    once = common.train_steps(_mlp_loss, params, _mlp_batches(), **kw)
    again = common.train_steps(_mlp_loss, params, _mlp_batches(), **kw)
    assert once == again
    # ``follow`` is handed the same arrays twice, as the controls' tests
    # hand them (``ref["init"]`` returns one set to the reference and to
    # its control)
    ref = {"init": lambda seed: common.flatten(params),
           "loss": lambda precision: _mlp_loss,
           "optimizer": optimizer, "lr": 1e-2}
    assert common.follow(ref, 0, _mlp_batches(), 2) == once
    assert common.follow(ref, 0, _mlp_batches(), 2) == once
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(before)):
        assert not got.is_deleted()
        assert np.array_equal(np.asarray(got), want)


def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


@pytest.mark.parametrize("rows_per_block", [4, 1])
@pytest.mark.parametrize("made_by", ["the caller", "the walk"])
def test_the_walk_holds_sixteen_bytes_a_parameter_at_an_update(
        monkeypatch, made_by, rows_per_block):
    """What is alive when an update is called, beyond what was alive before
    the walk, read through the walk's own seam: the ``OPTIMIZERS`` entry,
    patched to record and then call the real one.  Without ``jit`` every
    step calls it (under ``jit`` only a trace would), and what is alive
    then is what the walk holds by name: its own parameters (from the
    second step on; in the first it reads the caller's), Adam's two
    moments and one gradient, 16 bytes a float32 parameter, with the
    batch's last block and a few scalars.  The walk as it was held 20
    beyond the caller's from the second step on: the new parameters, the
    moments, the blocks' sum and their mean."""
    init, update = common.OPTIMIZERS["adam"]
    seen = []

    def recording(params, grads, state, **kw):
        seen.append(_live_bytes())
        return update(params, grads, state, **kw)

    monkeypatch.setitem(common.OPTIMIZERS, "adam", (init, recording))
    params, batches = _mlp_params(5, d=64, h=96), _mlp_batches(3, d=64)
    size = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    batch = sum(a.nbytes for a in batches[0])
    slack = batch + 4096
    if made_by == "the walk":
        host = jax.tree_util.tree_map(np.asarray, params)
        del params
        params = lambda: jax.tree_util.tree_map(jnp.asarray, host)  # noqa: E731
    before = _live_bytes()
    with jax.disable_jit():
        common.train_steps(_mlp_loss, params, batches, optimizer="adam",
                           lr=1e-2, rows_per_block=rows_per_block)
    held = [s - before for s in seen]
    assert len(held) == 3
    # first update: the walk's own are the moments and the gradient, and,
    # where it made them itself, the parameters
    first = 4 * size if made_by == "the walk" else 3 * size
    assert 3 * size <= held[0] <= first + slack
    # later updates: parameters, moments, gradient; the start weights the
    # walk made itself are gone
    assert all(4 * size <= h <= 4 * size + slack for h in held[1:]), (
        held, size)
