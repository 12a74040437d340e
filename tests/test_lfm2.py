"""The LFM2 decoder (``models/lfm2.py``) against the benchmark's plain
reference at toy size, float32 on both sides so that routing agrees:
parameter names and shapes, logits, three training steps' losses, every
leaf's first gradient and update for the stack, and the loss and every
leaf's gradient for each of the four layer kinds alone; the gated short
convolution against a direct loop over ``t``; causality; the layers' kinds
from the published ``layer_types``; the tied head; an expert layer's eight
shares; the selection bias; that a lower precision fails the comparison;
the model through ``make_train_step``."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.configs import lfm2_24b_a2b as adapter  # noqa: E402
from benchmarks.references import common, lfm2 as ref  # noqa: E402
from benchmarks.references.kanana2 import head_loss  # noqa: E402
from horovod_tpu import metrics  # noqa: E402
from horovod_tpu.models import lfm2 as model_lib  # noqa: E402
from horovod_tpu.models.gpt import next_token_loss  # noqa: E402
from horovod_tpu.models.qwen3_next import causal_depthwise_conv  # noqa: E402
from horovod_tpu.parallel.moe import (route_sigmoid_top_k,  # noqa: E402
                                      routed_experts)

CONV, ATTENTION = "conv", "full_attention"
PUBLISHED = [CONV, CONV, ATTENTION, CONV] * 10
CFG = {
    "layer_types": PUBLISHED, "first_layer": 1, "num_hidden_layers": 7,
    "num_dense_layers": 1, "hidden_size": 32, "intermediate_size": 48,
    "conv_L_cache": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "moe_intermediate_size": 16, "num_experts": 4, "router_num_experts": 16,
    "first_expert": 4, "num_experts_per_tok": 3,
    "routed_scaling_factor": 1, "norm_eps": 1e-05, "vocab_size": 96,
    "initializer_range": 0.02, "qk_norm_init": 2.0, "moe_group_rows": 48,
    "moe_capacity_factor": 1.0, "compute_dtype": "float32",
    "param_dtype": "float32", "optimizer": "adam", "learning_rate": 1e-4,
    "remat": "decoder_layer",
}
LENGTH = 48
MIX = {"arrays": [{"shape": [LENGTH]}]}
SEED = 2 ** 31 + 5


def _ids(seed, rows=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (rows, LENGTH)), jnp.int32)


def _setup(**changed):
    cfg = dict(CFG, **changed)
    model = adapter.program(cfg, MIX)["model"]
    return cfg, model, common.unflatten(ref.seeded_weights(cfg, SEED))


def _program_loss(model, ids):
    return lambda p: next_token_loss(model.apply({"params": p}, ids), ids)


@pytest.fixture(scope="module")
def setup():
    return _setup()


def test_reference_and_program_name_the_same_leaves(setup):
    cfg, model, params = setup
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            _ids(0))["params"]
    assert {k: v.shape for k, v in common.flatten(shapes).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()} \
        == ref.param_shapes(cfg)
    # the table is the head: no leaf of its own
    assert set(params) == {"embed_tokens", "embedding_norm",
                           *(f"layers_{i}" for i in range(7))}
    # a layer is two norms, an operator of its kind and a feed-forward part
    assert set(params["layers_0"]) == {"operator_norm", "conv", "ffn_norm",
                                       "feed_forward"}
    assert set(params["layers_0"]["conv"]) == {"in_proj", "conv", "out_proj"}
    assert set(params["layers_0"]["feed_forward"]) == {
        "gate_proj", "up_proj", "down_proj"}
    assert set(params["layers_1"]["self_attn"]) == {
        "q_proj", "k_proj", "v_proj", "q_layernorm", "k_layernorm",
        "out_proj"}
    assert set(params["layers_1"]["feed_forward"]) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj"}
    # no bias anywhere, the selection bias included (it is no parameter)
    assert not any("bias" in name for name in common.flatten(params))


@pytest.mark.parametrize("layers,kinds", [
    (7, [CONV, ATTENTION, CONV, CONV, CONV, ATTENTION, CONV]),
    (5, [CONV, ATTENTION, CONV, CONV, CONV])])
def test_the_layers_kinds_are_the_published_ones_from_layer_one(layers,
                                                                kinds):
    cfg = dict(CFG, num_hidden_layers=layers)
    assert list(ref.kinds(cfg)) == kinds
    model = adapter.program(cfg, MIX)["model"]
    assert list(model.kinds()) == kinds and model.num_dense_layers == 1
    assert list(model_lib.LAYER_TYPES) == PUBLISHED
    assert (PUBLISHED.count(CONV), PUBLISHED.count(ATTENTION)) == (30, 10)
    assert [i for i, k in enumerate(PUBLISHED) if k == ATTENTION] \
        == list(range(2, 40, 4))
    with pytest.raises(ValueError, match="layers"):
        ref.kinds(dict(cfg, num_hidden_layers=40))
    with pytest.raises(ValueError, match="operator"):
        model.clone(layer_types=(CONV, "sliding")).kinds()
    # the committed configuration holds the list whole and cuts by count
    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           "lfm2_24b_a2b.json")) as fh:
        real = json.load(fh)
    assert real["layer_types"] == PUBLISHED and real["first_layer"] == 1
    assert list(ref.kinds(real)) in (kinds, PUBLISHED[1:8], PUBLISHED[1:6])


def test_logits_match_the_reference(setup):
    cfg, model, params = setup
    got = model.apply({"params": params}, _ids(0))
    assert got.shape == (2, LENGTH, cfg["vocab_size"])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.logits_fn(cfg)(params, _ids(0))),
        atol=2e-6, rtol=2e-5)


# 48 rows x 3 picks / 16 experts = 9 a group when even: 1.0 leaves 9 and
# bites now and then, 0.4 leaves 4 and does, None is the dropless layer
@pytest.mark.parametrize("layers,factor", [(7, 1.0), (5, None), (5, 0.4)])
def test_three_steps_losses_gradients_and_updates_match_the_reference(
        layers, factor):
    """float32 on both sides: what differs is the order of the sums (the
    flash kernels' blocks, the experts' tiles), 1e-5 of a leaf's norm."""
    cfg, model, params = _setup(num_hidden_layers=layers,
                                moe_capacity_factor=factor)
    batches = [(np.asarray(_ids(10 + i)),) for i in range(3)]
    with common.full_precision():
        want = common.train_steps(
            ref.loss_fn(cfg), params, batches, optimizer="adam",
            lr=cfg["learning_rate"], rows_per_block=2)
    opt = optax.adam(cfg["learning_rate"])
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: next_token_loss(model.apply({"params": p}, ids),
                                       ids)))
    p, state, losses, first = params, opt.init(params), [], None
    for (ids,) in batches:
        loss, grads = grad_fn(p, jnp.asarray(ids))
        first = grads if first is None else first
        updates, state = opt.update(grads, state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    got_norms = common.leaf_norms(common.flatten(first))
    got_sketch = common.leaf_sketches(common.flatten(first))
    moved = common.leaf_diff_norms(common.flatten(p),
                                   common.flatten(params))
    for name, norm in want["grad_norms"].items():
        # every leaf gets a first gradient
        assert norm > 0, name
        assert abs(float(got_norms[name]) - norm) <= 1e-5 * norm, name
        # the sketches differ by the norm of the gradients' difference
        gap = np.sqrt(np.mean(np.square(
            np.asarray(got_sketch[name]) - want["grad_sketches"][name])))
        assert gap <= 1e-5 * norm, (name, gap / norm)
        update = want["update_norms"][name]
        assert abs(float(moved[name]) - update) <= 1e-4 * update, name
    if factor == 0.4:
        # the bound bites: the dropless model's loss is another
        free = _setup(num_hidden_layers=layers,
                      moe_capacity_factor=None)[1]
        ids = jnp.asarray(batches[0][0])
        assert abs(float(_program_loss(free, ids)(params))
                   - losses[0]) > 1e-6


def _leaf_by_leaf(cfg, model, params, ids, tolerance=1e-5):
    want_loss, want = jax.value_and_grad(ref.loss_fn(cfg))(params, ids)
    got_loss, got = jax.value_and_grad(_program_loss(model, ids))(params)
    assert abs(float(got_loss) - float(want_loss)) \
        <= tolerance * float(want_loss)
    want, got = common.flatten(want), common.flatten(got)
    assert set(got) == set(want)
    for name, w in want.items():
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(got[name] - w)) < tolerance * scale, \
            name


def test_every_gradient_leaf_matches_the_reference_leaf_by_leaf(setup):
    _leaf_by_leaf(*setup, _ids(3))


@pytest.mark.parametrize("dense", [1, 0], ids=["dense", "experts"])
@pytest.mark.parametrize("first,kind", [(1, CONV), (2, ATTENTION)])
def test_each_of_the_four_layer_kinds_alone_matches_the_reference(
        first, kind, dense):
    """Two operator kinds times two feed-forward kinds: one layer of each
    pair between the table and the tied head, the loss and every leaf's
    gradient (the source pairs a dense part with a ``conv`` operator only;
    the program and the reference take either)."""
    cfg, model, params = _setup(first_layer=first, num_hidden_layers=1,
                                num_dense_layers=dense)
    assert model.kinds() == (kind,) and model.num_dense_layers == dense
    assert ("conv" in params["layers_0"]) == (kind == CONV)
    assert ("gate" in params["layers_0"]["feed_forward"]) == (not dense)
    _leaf_by_leaf(cfg, model, params, _ids(6))


@pytest.mark.parametrize("at,factor", [(1, None), (17, 0.4), (40, None)])
def test_no_later_token_moves_an_earlier_logit(at, factor):
    """A causal convolution, causal attention, and a load bound that takes
    an expert's rows in row order: a row is never pushed out by a later
    one."""
    _, model, params = _setup(num_hidden_layers=3,
                              moe_capacity_factor=factor)
    ids = np.array(_ids(5, rows=1))
    before = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    ids[0, at:] = (ids[0, at:] + 7) % CFG["vocab_size"]
    after = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_array_equal(after[0, :at], before[0, :at])
    assert np.abs(after[0, at:] - before[0, at:]).max() > 1e-5


# -- the gated short convolution --------------------------------------------


def test_the_convolution_is_the_loop_over_t_by_hand(rng):
    """``z_t = sum_{j=0..2} w_j (B * x)_{t-2+j}`` a channel, zeros before
    the row's start, ``y = (C * z) W_out``: the reference's operator, the
    program's module and its ``causal_depthwise_conv`` against a loop over
    ``t`` in numpy (float64)."""
    b, s, d, taps = 2, 11, 8, 3
    mk = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    u, w_in, w, w_out = mk(b, s, d), mk(d, 3 * d), mk(taps, d), mk(d, d)
    bcx = u.astype(np.float64) @ w_in
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bx = gate_in * x
    z = np.zeros_like(bx)
    for t in range(s):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                z[:, t] += w[j] * bx[:, t - (taps - 1) + j]
    want = (gate_out * z) @ w_out
    np.testing.assert_allclose(
        np.asarray(causal_depthwise_conv(jnp.asarray(bx, jnp.float32),
                                         jnp.asarray(w))), z, rtol=1e-5,
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.causal_taps(jnp.asarray(bx, jnp.float32),
                                   jnp.asarray(w))), z, rtol=1e-5,
        atol=1e-5)
    p = {"in_proj": {"kernel": jnp.asarray(w_in)}, "conv": jnp.asarray(w),
         "out_proj": {"kernel": jnp.asarray(w_out)}}
    with common.full_precision():
        got_ref = ref.short_conv(jnp.asarray(u), p, {"hidden_size": d},
                                 lambda t: t)
        got = model_lib.ShortConv(taps=taps, dtype=jnp.float32).apply(
            {"params": p}, jnp.asarray(u))
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got_ref), want, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5 * scale)
    # the first row sees its own tap alone: w_2 (B * x)_0
    np.testing.assert_allclose(z[:, 0], w[2] * bx[:, 0], rtol=1e-12)


def test_the_head_is_the_table():
    """``logits = norm(h) @ table^T``: the table's gradient is the lookup's
    plus the head's."""
    cfg, model, params = _setup(num_hidden_layers=2)
    ids = _ids(1)
    grads = jax.grad(_program_loss(model, ids))(params)
    table = np.asarray(grads["embed_tokens"]["embedding"])
    # every id gets a gradient through the head, seen in the batch or not
    assert (np.abs(table).sum(axis=1) > 0).all()
    assert len(np.unique(np.asarray(ids))) < cfg["vocab_size"]
    hidden = ref.hidden_fn(cfg, lambda t: t)(params, ids)
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": params}, ids)),
        np.asarray(hidden @ params["embed_tokens"]["embedding"].T),
        atol=2e-6, rtol=2e-5)


# -- the router ------------------------------------------------------------------


def test_a_selection_bias_changes_the_picks_and_not_the_weights(rng):
    n, d, experts, top_k = 64, 32, 16, 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(0.3 * rng.normal(size=(d, experts)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)))
    bias = np.zeros(experts, np.float32)
    bias[[2, 9]] = 0.5, -0.5
    rule = functools.partial(route_sigmoid_top_k, scale=1.0,
                             eps=model_lib.ROUTE_EPS)
    w0, e0 = rule(x, router, top_k, bias=np.zeros(experts))
    w1, e1 = rule(x, router, top_k, bias=bias)
    e0, e1, w1 = np.asarray(e0), np.asarray(e1), np.asarray(w1)
    # the picks are the largest of score + bias, by hand
    np.testing.assert_array_equal(
        np.sort(e1, axis=1),
        np.sort(np.argsort(-(scores + bias), axis=1)[:, :top_k], axis=1))
    assert (np.sort(e0, axis=1) != np.sort(e1, axis=1)).any()
    assert (e1 == 2).sum() > (e0 == 2).sum()
    assert (e1 == 9).sum() < (e0 == 9).sum()
    # a pick's weight is its score without the bias, over the picks' sum
    # plus 1e-6
    picked = np.take_along_axis(scores, e1, axis=1)
    np.testing.assert_allclose(
        w1, picked / (picked.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-6)
    # a row whose picks the bias did not move weighs them as before
    same = (np.sort(e0, axis=1) == np.sort(e1, axis=1)).all(axis=1)
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(w0)[same], axis=1),
                               np.sort(w1[same], axis=1), rtol=1e-6)
    # the reference's dense form gives the same weights
    dense = np.asarray(ref.gate_weights(x, router, jnp.asarray(bias), top_k,
                                        1.0))
    np.testing.assert_allclose(np.take_along_axis(dense, e1, axis=1), w1,
                               rtol=1e-6)
    assert (np.count_nonzero(dense, axis=1) == top_k).all()
    # and the model takes one as a constant: other logits, the same leaves
    cfg, model, params = _setup(num_hidden_layers=2)
    values = [float(b) for b in np.linspace(
        -0.3, 0.3, cfg["router_num_experts"])]
    moved = model.clone(selection_bias=tuple(values))
    ids = _ids(4)
    assert float(jnp.max(jnp.abs(
        moved.apply({"params": params}, ids)
        - model.apply({"params": params}, ids)))) > 1e-6
    np.testing.assert_allclose(
        np.asarray(moved.apply({"params": params}, ids)),
        np.asarray(ref.logits_fn(dict(cfg, expert_bias=values))(params,
                                                                ids)),
        atol=2e-6, rtol=2e-5)
    assert jax.tree_util.tree_structure(jax.eval_shape(
        moved.init, jax.random.PRNGKey(0), ids)) \
        == jax.tree_util.tree_structure(jax.eval_shape(
            model.init, jax.random.PRNGKey(0), ids))


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rng):
    """Eight chips hold four of thirty-two experts each (``first_expert`` 0,
    4, 8, ...): the parts their ``routed_experts`` give under the sigmoid
    rule at eps 1e-6, and the reference's, add up to what the reference
    gives for the whole layer, without capacity.  There is no shared expert
    to count once."""
    d, f, experts, top_k, shares = 32, 16, 32, 4, 8
    cfg = dict(CFG, num_experts=experts, router_num_experts=experts,
               first_expert=0, hidden_size=d, moe_intermediate_size=f,
               num_experts_per_tok=top_k, moe_capacity_factor=None)
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x = mk(2, LENGTH, d)
    p = {"gate": mk(d, experts), "experts_gate_proj": mk(experts, d, f),
         "experts_up_proj": mk(experts, d, f),
         "experts_down_proj": mk(experts, f, d)}
    identity = lambda a: a  # noqa: E731
    whole = np.asarray(ref.moe(x, p, cfg, identity))
    assert np.abs(whole).max() > 1e-3
    route = functools.partial(
        route_sigmoid_top_k, bias=jnp.zeros(experts),
        scale=float(cfg["routed_scaling_factor"]), eps=model_lib.ROUTE_EPS)
    parts_ref, parts_program = np.zeros_like(whole), np.zeros_like(whole)
    held = experts // shares
    for share in range(shares):
        mine = {k: (v[share * held:(share + 1) * held]
                    if k.startswith("experts_") else v)
                for k, v in p.items()}
        part = np.asarray(ref.moe(
            x, mine, dict(cfg, num_experts=held, first_expert=share * held),
            identity))
        # a share alone is not the layer
        assert np.abs(part - whole).max() > 1e-4
        parts_ref = parts_ref + part
        parts_program = parts_program + np.asarray(routed_experts(
            x.reshape(-1, d), p["gate"],
            {k[len("experts_"):]: v for k, v in mine.items()
             if k.startswith("experts_")},
            top_k=top_k, first_expert=share * held,
            route=route)).reshape(x.shape)
    np.testing.assert_allclose(parts_ref, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(parts_program, whole, atol=2e-6, rtol=1e-5)


# -- a lower precision fails ---------------------------------------------------


def test_the_reference_with_bfloat16_operands_fails_the_comparison():
    """What the float32 comparison above holds (1e-5 of the loss and of
    every leaf's norm) a lower precision does not: the reference itself
    with every product's operands rounded to bfloat16 (8 bits of mantissa:
    4e-3 a value) is outside it by two orders, in the loss and in the worst
    leaf.  bfloat16 is the precision the cell states, so on the chip the
    comparison's limits are wider and the precision below it, float8, is
    what has to fail (``test_benchmark_lfm2.py``)."""
    cfg, _, params = _setup(num_hidden_layers=3)
    ids = _ids(3)

    def bf16(x):
        return x + jax.lax.stop_gradient(
            x.astype(jnp.bfloat16).astype(jnp.float32) - x)

    def loss_with(q):
        hidden = ref.hidden_fn(cfg, q)

        def loss(p, ids):
            b, s = ids.shape
            return head_loss(
                hidden(p, ids)[:, :-1].reshape(b * (s - 1), -1),
                p["embed_tokens"]["embedding"].T,
                ids[:, 1:].reshape(b * (s - 1)), q)
        return loss

    want_loss, want = jax.value_and_grad(loss_with(lambda t: t))(params, ids)
    assert float(want_loss) == float(ref.loss_fn(cfg)(params, ids))
    low_loss, low = jax.value_and_grad(loss_with(bf16))(params, ids)
    want, low = common.flatten(want), common.flatten(low)
    gaps = {name: float(jnp.linalg.norm(low[name] - w)
                        / jnp.linalg.norm(w)) for name, w in want.items()}
    assert max(gaps.values()) > 1e-3, max(gaps.values())
    assert sum(g > 1e-5 for g in gaps.values()) > len(gaps) // 2
    assert abs(float(low_loss) - float(want_loss)) > 1e-6 * float(want_loss)


# -- the model's own sizes and seeds ---------------------------------------------


def test_the_model_groups_its_rows_and_seeds_as_the_reference_does():
    cfg, model, params = _setup(num_hidden_layers=2)
    assert (model.moe_group_rows, model.moe_capacity_factor) == (48, 1.0)
    with pytest.raises(ValueError, match="whole groups"):
        model.clone(moe_group_rows=80).apply({"params": params}, _ids(0))
    own = model.init(jax.random.PRNGKey(0), _ids(0))["params"]
    theirs = common.unflatten(ref.seeded_weights(cfg, SEED))
    for tree in (own, theirs):
        attn, conv = tree["layers_1"]["self_attn"], tree["layers_0"]["conv"]
        for leaf in (attn["q_proj"]["kernel"], attn["out_proj"]["kernel"],
                     conv["in_proj"]["kernel"], conv["out_proj"]["kernel"],
                     tree["layers_1"]["feed_forward"]["experts_down_proj"],
                     tree["layers_1"]["feed_forward"]["gate"],
                     tree["embed_tokens"]["embedding"]):
            assert abs(float(jnp.std(leaf)) - 0.02) < 0.004
        assert abs(float(jnp.std(conv["conv"])) - 0.02) < 0.01
        # the heads' norms start at the softmax's temperature, the others
        # at one
        for name in ("q_layernorm", "k_layernorm"):
            np.testing.assert_array_equal(
                np.asarray(attn[name]["weight"]), np.full(8, 2.0))
        np.testing.assert_array_equal(
            np.asarray(tree["layers_1"]["operator_norm"]["weight"]),
            np.ones(32))
    # the published widths are the model's defaults
    full = model_lib.Lfm2()
    assert (full.hidden_size, full.intermediate_size, full.conv_taps,
            full.num_heads, full.num_kv_heads, full.head_dim,
            full.num_experts_per_tok, full.moe_intermediate_size,
            full.router_experts, full.vocab_size, full.num_dense_layers,
            len(full.kinds()), full.norm_eps, full.rope_theta) == (
        2048, 11776, 3, 32, 8, 64, 4, 1536, 64, 65536, 2, 40, 1e-5, 1e6)


# -- through the step builder ----------------------------------------------------


def test_the_model_trains_through_make_train_step(hvd_init, monkeypatch):
    """``init_train_state`` / ``make_train_step`` take it as they take the
    other language models; the convolution operators are counted by their
    taps and channels and the expert layers by their routing rule."""
    import horovod_tpu as hvd
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read(name, **labels):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get(name, {}).get("samples", [])
            if all(s["labels"].get(k) == v for k, v in labels.items()))

    model = model_lib.lfm2_tiny(dtype=jnp.float32)
    assert model.kinds() == (CONV, ATTENTION, CONV, CONV, CONV)
    opt = optax.adam(1e-3)
    sconv = dict(taps="3", channels="64")
    rule = dict(held="4", top_k="2", rule="route_sigmoid_top_k", groups="1")
    before = (read("hvd_sconv_layers_traced_total", **sconv),
              read("hvd_moe_layers_traced_total", **rule))
    state = init_train_state(model, opt, jnp.zeros((1, 32), jnp.int32))
    step = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=next_token_loss, optimizer=opt)
    ids = shard_batch(np.random.default_rng(0).integers(
        0, 256, (hvd.size(), 32)).astype(np.int32))
    losses = []
    for _ in range(3):
        state, loss = step(state, ids, ids)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[2] < losses[0]
    assert read("hvd_sconv_layers_traced_total", **sconv) - before[0] >= 4
    assert read("hvd_moe_layers_traced_total", **rule) - before[1] >= 4
