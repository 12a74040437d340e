"""The latent-attention decoder (``models/kanana2.py``) against the
benchmark's plain reference at toy size, float32 on both sides so that
routing agrees: parameter names and shapes, logits, three training steps'
losses, every leaf's first gradient and update, with the experts' load
bound biting and without; causality; the sigmoid router's bias, scale and
normalisation by hand; the expert layer's sixteen shares; the model through
``make_train_step``."""

import functools
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

from benchmarks.configs import kanana2_30b_a3b as adapter  # noqa: E402
from benchmarks.references import common, kanana2 as ref  # noqa: E402
from horovod_tpu import metrics  # noqa: E402
from horovod_tpu.models import kanana2 as model_lib  # noqa: E402
from horovod_tpu.models.gpt import next_token_loss  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from horovod_tpu.parallel.moe import (route_sigmoid_top_k, route_top_k,  # noqa: E402
                                      routed_experts)

CFG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 32,
    "intermediate_size": 48, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 24, "rope_theta": 10000, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "router_num_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-06, "vocab_size": 96,
    "initializer_range": 0.02, "q_proj_initializer_range": 0.1,
    "moe_group_rows": 48, "moe_capacity_factor": 1.25,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 1e-4, "remat": "decoder_layer",
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
    # layer 0 is dense, the others hold a router, experts and shared experts
    assert set(params["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                              "down_proj"}
    assert set(params["layers_1"]["mlp"]) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj",
        "shared_experts_gate_proj", "shared_experts_up_proj",
        "shared_experts_down_proj"}
    # the selection bias is no parameter
    assert not any("bias" in name for name in common.flatten(params))


def test_logits_match_the_reference(setup):
    cfg, model, params = setup
    got = model.apply({"params": params}, _ids(0))
    assert got.shape == (2, LENGTH, cfg["vocab_size"])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.logits_fn(cfg)(params, _ids(0))),
        atol=2e-6, rtol=2e-5)


# 48 rows x 3 picks / 16 experts = 9 a group when even: 1.25 leaves 12 and
# rarely bites, 0.4 leaves 4 and does, None is the dropless layer
@pytest.mark.parametrize("factor", [None, 1.25, 0.4])
def test_three_steps_losses_gradients_and_updates_match_the_reference(
        factor):
    cfg, model, params = _setup(moe_capacity_factor=factor)
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
        free = _setup(moe_capacity_factor=None)[1]
        ids = jnp.asarray(batches[0][0])
        assert abs(float(next_token_loss(
            free.apply({"params": params}, ids), ids)) - losses[0]) > 1e-6


def test_every_gradient_leaf_matches_the_reference_leaf_by_leaf(setup):
    cfg, model, params = setup
    ids = _ids(3)
    want = common.flatten(jax.grad(ref.loss_fn(cfg))(params, ids))
    got = common.flatten(jax.grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params))
    for name, w in want.items():
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(got[name] - w)) < 1e-5 * scale, name


@pytest.mark.parametrize("factor", [None, 0.4])
@pytest.mark.parametrize("at", [1, 17, 40])
def test_no_later_token_moves_an_earlier_logit(at, factor):
    """Causal attention, and a load bound that takes an expert's rows in
    row order: a row is never pushed out by a later one."""
    _, model, params = _setup(moe_capacity_factor=factor)
    ids = np.array(_ids(5, rows=1))
    before = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    ids[0, at:] = (ids[0, at:] + 7) % CFG["vocab_size"]
    after = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_array_equal(after[0, :at], before[0, :at])
    assert np.abs(after[0, at:] - before[0, at:]).max() > 1e-5


# -- the routing rule -----------------------------------------------------------


def test_a_selection_bias_changes_the_picks_and_not_the_weights(rng):
    n, d, experts, top_k, scale = 64, 32, 16, 3, 2.448
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(0.3 * rng.normal(size=(d, experts)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, router, precision=jax.lax.Precision.HIGHEST)))
    bias = np.zeros(experts, np.float32)
    bias[[2, 9]] = 0.5, -0.5
    w0, e0 = route_sigmoid_top_k(x, router, top_k, bias=np.zeros(experts),
                                 scale=scale)
    w1, e1 = route_sigmoid_top_k(x, router, top_k, bias=bias, scale=scale)
    e0, e1, w1 = np.asarray(e0), np.asarray(e1), np.asarray(w1)
    # the picks are the largest of score + bias, by hand
    np.testing.assert_array_equal(
        np.sort(e1, axis=1),
        np.sort(np.argsort(-(scores + bias), axis=1)[:, :top_k], axis=1))
    assert (np.sort(e0, axis=1) != np.sort(e1, axis=1)).any()
    assert (e1 == 2).sum() > (e0 == 2).sum()
    assert (e1 == 9).sum() < (e0 == 9).sum()
    # a pick's weight is its score without the bias, over the picks' sum
    picked = np.take_along_axis(scores, e1, axis=1)
    np.testing.assert_allclose(
        w1, scale * picked / picked.sum(axis=1, keepdims=True), rtol=1e-6)
    # a row whose picks the bias did not move weighs them as before
    same = (np.sort(e0, axis=1) == np.sort(e1, axis=1)).all(axis=1)
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(w0)[same], axis=1),
                               np.sort(w1[same], axis=1), rtol=1e-6)
    # and the model takes one as a constant: other logits, the same leaves
    cfg, model, params = _setup()
    moved = model.clone(selection_bias=tuple(
        float(b) for b in np.linspace(-0.3, 0.3, cfg["router_num_experts"])))
    ids = _ids(4)
    assert float(jnp.max(jnp.abs(
        moved.apply({"params": params}, ids)
        - model.apply({"params": params}, ids)))) > 1e-6
    np.testing.assert_allclose(
        np.asarray(moved.apply({"params": params}, ids)), np.asarray(
            ref.logits_fn(dict(cfg, e_score_correction_bias=list(
                moved.selection_bias)))(params, ids)), atol=2e-6, rtol=2e-5)


def test_the_scale_and_the_normalisation_by_hand_on_one_row():
    """Scores 0.9, 0.8, 0.7, 0.6 and the rest lower, top 3: the weights are
    2.448 x (0.9, 0.8, 0.7) / 2.4."""
    scores = np.array([0.2, 0.9, 0.1, 0.7, 0.3, 0.8, 0.6, 0.05])
    logits = np.log(scores / (1 - scores))
    x = jnp.asarray([[1.0, 0.0]], jnp.float32)
    router = jnp.asarray(np.stack([logits, np.zeros(8)]), jnp.float32)
    w, e = route_sigmoid_top_k(x, router, 3, bias=np.zeros(8), scale=2.448)
    assert np.asarray(e).tolist() == [[1, 5, 3]]
    np.testing.assert_allclose(
        np.asarray(w)[0], 2.448 * np.array([0.9, 0.8, 0.7]) / 2.4, rtol=1e-6)
    # the reference's dense form of the same row
    dense = np.asarray(ref.gate_weights(x, router, jnp.zeros(8), 3, 2.448))
    np.testing.assert_allclose(dense[0, [1, 5, 3]], np.asarray(w)[0],
                               rtol=1e-6)
    assert not dense[0, [0, 2, 4, 6, 7]].any()
    # softmax stays the default rule and is another
    w_soft, _ = route_top_k(x, router, 3)
    np.testing.assert_allclose(float(jnp.sum(w_soft)), 1.0, rtol=1e-6)
    assert abs(float(jnp.sum(w)) - 2.448) < 1e-5


# -- the expert layer's shares --------------------------------------------------


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rng):
    """Sixteen chips hold two of thirty-two experts each: the parts their
    ``routed_experts`` give under the sigmoid rule, and the reference's,
    with the shared experts counted once, add up to what the reference
    gives for the whole layer, without capacity."""
    d, f, experts, top_k, shares = 32, 16, 32, 6, 16
    cfg = dict(CFG, n_routed_experts=experts, router_num_experts=experts,
               first_expert=0, hidden_size=d, moe_intermediate_size=f,
               num_experts_per_tok=top_k, moe_capacity_factor=None)
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x = mk(2, LENGTH, d)
    p = {"gate": mk(d, experts), "experts_gate_proj": mk(experts, d, f),
         "experts_up_proj": mk(experts, d, f),
         "experts_down_proj": mk(experts, f, d),
         **{f"shared_experts_{k}_proj": {"kernel": mk(*s)} for k, s in (
             ("gate", (d, 2 * f)), ("up", (d, 2 * f)),
             ("down", (2 * f, d)))}}
    identity = lambda a: a  # noqa: E731
    whole = np.asarray(ref.moe(x, p, cfg, identity))
    shared = whole - np.asarray(ref.moe(x, p, cfg, identity, shared=False))
    assert np.abs(shared).max() > 1e-3
    route = functools.partial(route_sigmoid_top_k,
                              bias=jnp.zeros(experts),
                              scale=cfg["routed_scaling_factor"])
    parts_ref, parts_program = shared.copy(), shared.copy()
    held = experts // shares
    for share in range(shares):
        mine = {k: (v[share * held:(share + 1) * held]
                    if k.startswith("experts_") else v)
                for k, v in p.items()}
        parts_ref = parts_ref + np.asarray(ref.moe(
            x, mine, dict(cfg, n_routed_experts=held,
                          first_expert=share * held), identity,
            shared=False))
        parts_program = parts_program + np.asarray(routed_experts(
            x.reshape(-1, d), p["gate"],
            {k[len("experts_"):]: v for k, v in mine.items()
             if k.startswith("experts_")},
            top_k=top_k, first_expert=share * held,
            route=route)).reshape(x.shape)
    np.testing.assert_allclose(parts_ref, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(parts_program, whole, atol=2e-6, rtol=1e-5)


def test_the_seam_leaves_the_softmax_layer_as_it_was(rng, monkeypatch):
    """``route=route_top_k`` is the default: naming it changes nothing, and
    the sigmoid rule goes through the same sort, tiles and capacity."""
    monkeypatch.setattr(moe, "TILE", 8)
    n, d, f, experts, held, top_k = 96, 32, 16, 8, 4, 3
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x, router = mk(n, d), mk(d, experts)
    p = {"gate_proj": mk(held, d, f), "up_proj": mk(held, d, f),
         "down_proj": mk(held, f, d)}
    kw = dict(top_k=top_k, first_expert=2)
    np.testing.assert_array_equal(
        np.asarray(routed_experts(x, router, p, **kw)),
        np.asarray(routed_experts(x, router, p, route=route_top_k, **kw)))
    route = functools.partial(route_sigmoid_top_k, bias=jnp.zeros(experts),
                              scale=2.0)
    for capacity in (None, 5):
        gates = ref.gate_weights(x, router, jnp.zeros(experts), top_k,
                                 2.0)[:, 2:2 + held]
        if capacity is not None:
            gates = ref.bounded(gates, n, capacity)
        hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", x, p["gate_proj"])) \
            * jnp.einsum("nd,edf->enf", x, p["up_proj"])
        want = jnp.einsum("ne,enf,efd->nd", gates, hidden, p["down_proj"])
        np.testing.assert_allclose(
            np.asarray(routed_experts(x, router, p, capacity=capacity,
                                      route=route, **kw)),
            np.asarray(want), atol=2e-6, rtol=1e-5)


# -- the parts of the mixer -------------------------------------------------------


def test_interleaved_rotary_rotates_the_pairs_2i_2i_plus_1(rng):
    """The program hands back the rotated pairs' first members, then their
    second; the reference rotates in place: the same numbers, and the same
    products between two rotated vectors."""
    x = jnp.asarray(rng.normal(size=(1, 12, 2, 8)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(1, 12, 2, 8)), jnp.float32)
    got = np.asarray(model_lib.interleaved_rotary(x, jnp.arange(12), 1e4))
    want = np.asarray(ref._rotary(x, 1e4))
    np.testing.assert_allclose(got[..., :4], want[..., 0::2], atol=1e-6)
    np.testing.assert_allclose(got[..., 4:], want[..., 1::2], atol=1e-6)
    # by hand: pair i of position t turns by t * theta ** (-2i / 8)
    t, i = 5, 2
    angle = t * 1e4 ** (-2 * i / 8)
    a, b = np.asarray(x)[0, t, 1, 2 * i], np.asarray(x)[0, t, 1, 2 * i + 1]
    np.testing.assert_allclose(
        want[0, t, 1, 2 * i:2 * i + 2],
        [a * np.cos(angle) - b * np.sin(angle),
         b * np.cos(angle) + a * np.sin(angle)], rtol=1e-5, atol=1e-6)
    # position 0 is left as it is
    np.testing.assert_allclose(want[0, 0], np.asarray(x)[0, 0], atol=1e-7)
    got_y = np.asarray(model_lib.interleaved_rotary(y, jnp.arange(12), 1e4))
    np.testing.assert_allclose(
        np.einsum("bshd,bthd->bhst", got, got_y),
        np.einsum("bshd,bthd->bhst", want, np.asarray(ref._rotary(y, 1e4))),
        atol=1e-5)


def test_reference_attention_in_blocks_is_the_softmax_unblocked(rng):
    from horovod_tpu.ops.flash_attention import softmax_attention

    q, k = (jnp.asarray(rng.normal(size=(2, 32, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
    whole = softmax_attention(q, k, v, causal=True)
    for head_block, query_block in ((4, 32), (2, 8), (1, 16)):
        np.testing.assert_allclose(
            np.asarray(ref.causal_attention(q, k, v, lambda a: a, head_block,
                                            query_block)),
            np.asarray(whole), atol=2e-6)
    with pytest.raises(ValueError, match="whole blocks"):
        ref.causal_attention(q, k, v, lambda a: a, 3, 8)


def test_the_model_seeds_its_own_q_projection_and_groups_its_rows(setup):
    cfg, model, params = setup
    assert (model.moe_group_rows, model.moe_capacity_factor) == (48, 1.25)
    own = model.init(jax.random.PRNGKey(0), _ids(0))["params"]["layers_1"]
    q_std = float(jnp.std(own["self_attn"]["q_proj"]["kernel"]))
    o_std = float(jnp.std(own["self_attn"]["o_proj"]["kernel"]))
    assert abs(q_std - cfg["q_proj_initializer_range"]) < 0.01
    assert abs(o_std - cfg["initializer_range"]) < 0.005
    assert float(own["self_attn"]["kv_a_layernorm"]["weight"][3]) == 1
    with pytest.raises(ValueError, match="whole groups"):
        model.clone(moe_group_rows=80).apply({"params": params}, _ids(0))


# -- through the step builder ------------------------------------------------------


def test_the_model_trains_through_make_train_step(hvd_init, monkeypatch):
    """``init_train_state`` / ``make_train_step`` take it as they take the
    other language models; the layers are counted by their widths and the
    expert layers by their routing rule."""
    import horovod_tpu as hvd
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read(name, **labels):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get(name, {}).get("samples", [])
            if all(s["labels"].get(k) == v for k, v in labels.items()))

    model = model_lib.kanana2_tiny(dtype=jnp.float32)
    opt = optax.adam(1e-3)
    mla = dict(qk="24", v="16", latent="32")
    rule = dict(held="4", top_k="2", rule="route_sigmoid_top_k")
    before = (read("hvd_mla_layers_traced_total", **mla),
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
    assert read("hvd_mla_layers_traced_total", **mla) - before[0] >= 3
    assert read("hvd_moe_layers_traced_total", **rule) - before[1] >= 2
    assert read("hvd_moe_layers_traced_total", rule="route_top_k") >= 0
