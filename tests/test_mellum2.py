"""The decoder whose layers differ by attention kind (``models/mellum2.py``)
against the benchmark's plain reference at toy size, float32 on both sides
so that routing agrees: parameter names and shapes, logits, three training
steps' losses, every leaf's first gradient and update, with the experts'
load bound biting and without, and in bfloat16 within a band; causality and
the window's reach; YaRN's ramp and frequencies against hand numbers; the
expert layer's eight shares; the model through ``make_train_step``."""

import math
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

from benchmarks.configs import mellum2_12b_a2p5b as adapter  # noqa: E402
from benchmarks.references import common, mellum2 as ref  # noqa: E402
from horovod_tpu import metrics  # noqa: E402
from horovod_tpu.models import mellum2 as model_lib  # noqa: E402
from horovod_tpu.models.gpt import next_token_loss  # noqa: E402
from horovod_tpu.models.qwen3_next import apply_rotary  # noqa: E402
from horovod_tpu.parallel.moe import routed_experts  # noqa: E402

SLIDING, FULL = "sliding_attention", "full_attention"
#: the source's rotary parameters, the full layers' original context cut to
#: the toy's (so that the ramp lies inside a head of 16: pairs 0 to 2)
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
           "original_max_position_embeddings": 32, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 10000},
}
CFG = {
    "num_hidden_layers": 4, "layer_types": [SLIDING, SLIDING, SLIDING, FULL]
    * 2, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 12,
    "rope_parameters": ROPE, "moe_intermediate_size": 16, "num_experts": 4,
    "router_num_experts": 16, "first_expert": 4, "num_experts_per_tok": 3,
    "rms_norm_eps": 1e-06, "vocab_size": 96, "initializer_range": 0.02,
    "q_proj_initializer_range": 0.1, "moe_group_rows": 48,
    "moe_capacity_factor": 1.25, "compute_dtype": "float32",
    "param_dtype": "float32", "optimizer": "adam", "learning_rate": 1e-4,
    "remat": "decoder_layer",
}
LENGTH = 48
MIX = {"arrays": [{"shape": [LENGTH]}]}
SEED = 2 ** 31 + 5
#: The published rotary parameters (config.json's ``rope_parameters``)
PUBLISHED = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


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
    # a window layer and a full layer hold the same leaves: the kind is in
    # the mask and the table, not in a parameter
    assert set(params["layers_0"]["self_attn"]) == set(
        params["layers_3"]["self_attn"]) == {"q_proj", "k_proj", "v_proj",
                                             "o_proj"}
    assert set(params["layers_0"]["mlp"]) == {
        "gate", "experts_gate_proj", "experts_up_proj", "experts_down_proj"}
    assert model.kinds() == (SLIDING, SLIDING, SLIDING, FULL)


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
    """Tolerances: both sides are float32 with the same picks, so what is
    left is the order of the sums (flash attention's online softmax against
    the materialised one, tiles of rows against a dense gate matrix):
    1e-5 of a leaf's norm on the first gradient, ten times that on three
    steps of Adam, whose division by the gradient's own magnitude enlarges
    it."""
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
    assert set(got) == set(want) == set(ref.param_shapes(cfg))
    for name, w in want.items():
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(got[name] - w)) < 1e-5 * scale, name


@pytest.mark.parametrize("batch", [3, 4, 5])
def test_in_bfloat16_the_program_stays_within_a_band_of_the_reference(batch):
    """The cell computes in bfloat16 (8 bits of mantissa), where the
    float32 program reads 1e-5.  The band, with what three batches read at
    this size: the loss within 2e-4 of the float32 reference's (3e-6 to
    2e-5); every leaf's first gradient within half its norm (a gradient of
    half the size, or one missing, reads 0.5 and more; the worst leaves are
    routers and experts at 0.11 to 0.25, because a rounded score flips a
    pick and the row's whole term moves to another expert), the leaves
    outside the expert layers within 0.2 (the worst is the norm that feeds
    layer 0's experts, 0.11), and the mean over the leaves, what the
    benchmark's ``grad_sketch_gap`` estimates, under 0.1 (0.02 to 0.045).
    The dropless layer, so that a pick that flips at the
    capacity's edge is not in the band too."""
    cfg, _, params = _setup(moe_capacity_factor=None)
    model = adapter.program(dict(cfg, compute_dtype="bfloat16"),
                            MIX)["model"]
    assert model.dtype == jnp.bfloat16
    ids = _ids(batch)
    loss, got = jax.value_and_grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params)
    want_loss, want = jax.value_and_grad(ref.loss_fn(cfg))(params, ids)
    assert 0 < abs(float(loss) - float(want_loss)) < 2e-4 * float(want_loss)
    got, want = common.flatten(got), common.flatten(want)
    gaps = {}
    for name, w in want.items():
        assert got[name].dtype == jnp.float32
        gaps[name] = float(jnp.linalg.norm(got[name] - w)) / float(
            jnp.linalg.norm(w))
        assert gaps[name] < (0.5 if "/mlp/" in name else 0.2), (
            name, gaps[name])
    assert 1e-4 < sum(gaps.values()) / len(gaps) < 0.1


@pytest.mark.parametrize("factor", [None, 0.4])
@pytest.mark.parametrize("at", [1, 17, 40])
def test_no_later_token_moves_an_earlier_logit(at, factor):
    """Both masks are causal, and the load bound takes an expert's rows in
    row order: a row is never pushed out by a later one."""
    _, model, params = _setup(moe_capacity_factor=factor)
    ids = np.array(_ids(5, rows=1))
    before = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    ids[0, at:] = (ids[0, at:] + 7) % CFG["vocab_size"]
    after = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_array_equal(after[0, :at], before[0, :at])
    assert np.abs(after[0, at:] - before[0, at:]).max() > 1e-5


def test_a_window_layer_sees_its_window_and_a_full_layer_everything():
    """One layer of each kind, the expert layer dropless (a bound couples
    rows through their order): moving token 0 moves row ``t`` of a window
    layer only while ``t - 0 < window``; the full layer's last row moves
    too."""
    window = CFG["sliding_window"]
    for kind, reaches in ((SLIDING, window), (FULL, LENGTH)):
        _, model, params = _setup(num_hidden_layers=1, layer_types=[kind],
                                  moe_capacity_factor=None)
        ids = np.array(_ids(6, rows=1))
        before = np.asarray(model.apply({"params": params},
                                        jnp.asarray(ids)))
        ids[0, 0] = (ids[0, 0] + 5) % CFG["vocab_size"]
        after = np.asarray(model.apply({"params": params},
                                       jnp.asarray(ids)))
        moved = np.abs(after[0] - before[0]).max(axis=-1) > 0
        assert moved[:reaches].all(), kind
        assert not moved[reaches:].any(), kind
    # Hugging Face's form of the same window
    i, j = np.arange(LENGTH)[:, None], np.arange(LENGTH)[None, :]
    np.testing.assert_array_equal(
        np.asarray(ref.allowed_pairs(SLIDING, LENGTH, window)),
        (j <= i) & (j > i - window))
    np.testing.assert_array_equal(
        np.asarray(ref.allowed_pairs(FULL, LENGTH, window)), j <= i)


# -- rotary embedding, a table a kind --------------------------------------------


def test_yarn_ramp_and_frequencies_against_hand_numbers():
    """The published full-attention parameters: theta 500 000, factor 16
    over 8192 positions, beta 32 and 1, head 128.  ``dim(r) = 128 ln(8192 /
    (2 pi r)) / (2 ln 500000)``: ``dim(32)`` = 18.08 and ``dim(1)`` = 34.98,
    so the ramp runs from pair 18 to pair 35: pairs 0-18 keep their
    frequency, pairs 35-63 are divided by 16, pair ``c`` between is ``e_c
    (1 - r + r / 16)`` with ``r = (c - 18) / 17``."""
    rope = PUBLISHED[FULL]
    assert math.isclose(128 * math.log(8192 / (2 * math.pi * 32))
                        / (2 * math.log(500000)), 18.08, abs_tol=0.01)
    assert math.isclose(128 * math.log(8192 / (2 * math.pi))
                        / (2 * math.log(500000)), 34.98, abs_tol=0.01)
    assert ref.yarn_range(rope, 128) == (18, 35)
    assert model_lib.yarn_correction_range(128, 5e5, 8192, 32, 1) == (18, 35)
    want = []
    for c in range(64):
        e = 500000 ** (-2 * c / 128)
        r = min(max((c - 18) / 17, 0.0), 1.0)
        want.append(e / 16 * r + e * (1 - r))
    got = np.asarray(ref.inv_freq(rope, 128))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the first and the last, and the ramp's two ends, as numbers
    assert got[0] == 1.0
    np.testing.assert_allclose(got[63], 500000 ** (-126 / 128) / 16,
                               rtol=2e-6)
    np.testing.assert_allclose(got[63], 1.5344630e-07, rtol=1e-5)
    np.testing.assert_allclose(got[18], 500000 ** (-36 / 128), rtol=2e-6)
    np.testing.assert_allclose(got[18], 0.024955412, rtol=1e-5)
    np.testing.assert_allclose(got[35], 500000 ** (-70 / 128) / 16,
                               rtol=2e-6)
    np.testing.assert_allclose(got[35], 4.7781063e-05, rtol=1e-5)
    np.testing.assert_allclose(got[26], 500000 ** (-52 / 128)
                               * (1 - 8 / 17 + 8 / 17 / 16), rtol=2e-6)
    # the window layers' are the plain ones
    np.testing.assert_allclose(
        np.asarray(ref.inv_freq(PUBLISHED[SLIDING], 128)),
        [500000 ** (-2 * c / 128) for c in range(64)], rtol=2e-6)
    # the attention factor is 0.1 ln(16) + 1, on cos and on sin
    assert math.isclose(rope["attention_factor"], 0.1 * math.log(16) + 1)
    cos, sin = ref.rotary_table(rope, 128, 4)
    np.testing.assert_allclose(np.asarray(cos[0]),
                               rope["attention_factor"], rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(sin[3]), rope["attention_factor"] * np.sin(3 * got),
        rtol=1e-5, atol=1e-9)
    # the program's frequencies are the reference's
    np.testing.assert_allclose(
        np.asarray(model_lib.rotary_frequencies(
            128, 5e5, yarn_factor=16.0, original=8192)), got, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(model_lib.rotary_frequencies(128, 5e5)),
        np.asarray(ref.inv_freq(PUBLISHED[SLIDING], 128)), rtol=1e-6)


def test_the_models_tables_are_the_references_a_kind(rng):
    """Two tables, made once: the window layers' plain, the full layers'
    YaRN's with the attention factor on both; the program's ``rotate_half``
    form gives the reference's rotated q, and a full layer's q . k carries
    the factor squared."""
    _, model, _ = _setup()
    tables = model.rotary_tables(jnp.arange(LENGTH))
    assert set(tables) == {SLIDING, FULL}
    x = jnp.asarray(rng.normal(size=(1, LENGTH, 2, 16)), jnp.float32)
    for kind in (SLIDING, FULL):
        want = ref._rotary(x, ref.rotary_table(ROPE[kind], 16, LENGTH))
        np.testing.assert_allclose(
            np.asarray(apply_rotary(x, *tables[kind])), np.asarray(want),
            atol=2e-6)
    # position 0 is scaled and not turned
    np.testing.assert_allclose(
        np.asarray(apply_rotary(x, *tables[FULL]))[0, 0],
        ROPE[FULL]["attention_factor"] * np.asarray(x)[0, 0], rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(apply_rotary(x, *tables[SLIDING]))[0, 0],
        np.asarray(x)[0, 0], rtol=1e-6)
    # no YaRN: the full layers take the window layers' table
    plain = model.clone(yarn_factor=None).rotary_tables(jnp.arange(LENGTH))
    np.testing.assert_array_equal(np.asarray(plain[FULL][0]),
                                  np.asarray(plain[SLIDING][0]))
    np.testing.assert_array_equal(np.asarray(plain[SLIDING][1]),
                                  np.asarray(tables[SLIDING][1]))
    with pytest.raises(ValueError, match="as many kinds"):
        model.clone(layer_types=(SLIDING, "chunked", FULL, FULL)).kinds()
    with pytest.raises(ValueError, match="as many kinds"):
        model.clone(layer_types=(SLIDING,)).kinds()
    assert model_lib.Mellum2(num_layers=8).kinds() == (
        SLIDING, SLIDING, SLIDING, FULL) * 2


# -- the expert layer's shares --------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rng):
    """Eight chips hold eight of sixty-four experts each (``first_expert``
    0, 8, ..., 56): the parts their ``routed_experts`` give, and the
    reference's, add up to what the reference gives for the whole layer,
    without capacity.  No shared expert: nothing is counted once."""
    d, f, experts, top_k, shares = 32, 16, 64, 8, 8
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
    # every row's eight weights add up to one (norm_topk_prob)
    gates = np.asarray(ref.gate_weights(x.reshape(-1, d), p["gate"], top_k))
    assert ((gates > 0).sum(axis=1) == top_k).all()
    np.testing.assert_allclose(gates.sum(axis=1), 1.0, rtol=1e-6)
    parts_ref, parts_program = np.zeros_like(whole), np.zeros_like(whole)
    held = experts // shares
    for share in range(shares):
        mine = {k: (v[share * held:(share + 1) * held]
                    if k.startswith("experts_") else v)
                for k, v in p.items()}
        parts_ref = parts_ref + np.asarray(ref.moe(
            x, mine, dict(cfg, num_experts=held, first_expert=share * held),
            identity))
        parts_program = parts_program + np.asarray(routed_experts(
            x.reshape(-1, d), p["gate"],
            {k[len("experts_"):]: v for k, v in mine.items()
             if k.startswith("experts_")},
            top_k=top_k, first_expert=share * held)).reshape(x.shape)
    np.testing.assert_allclose(parts_ref, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(parts_program, whole, atol=2e-6, rtol=1e-5)


def test_the_model_seeds_its_own_q_projection_and_groups_its_rows(setup):
    cfg, model, params = setup
    assert (model.moe_group_rows, model.moe_capacity_factor) == (48, 1.25)
    assert (model.first_expert, model.num_experts, model.router_experts) \
        == (4, 4, 16)
    own = model.init(jax.random.PRNGKey(0), _ids(0))["params"]
    for layer in ("layers_1", "layers_3"):      # a window and a full layer
        attn = own[layer]["self_attn"]
        assert abs(float(jnp.std(attn["q_proj"]["kernel"]))
                   - cfg["q_proj_initializer_range"]) < 0.01
        assert abs(float(jnp.std(attn["o_proj"]["kernel"]))
                   - cfg["initializer_range"]) < 0.005
    assert float(own["layers_0"]["input_layernorm"]["weight"][3]) == 1
    with pytest.raises(ValueError, match="whole groups"):
        model.clone(moe_group_rows=80).apply({"params": params}, _ids(0))


# -- through the step builder ------------------------------------------------------


def test_the_model_trains_through_make_train_step(hvd_init, monkeypatch):
    """``init_train_state`` / ``make_train_step`` take it as they take the
    other language models; the layers are counted by kind, the flash
    kernels' tiles by mask, the expert layers by their routing rule."""
    import horovod_tpu as hvd
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read(name, **labels):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get(name, {}).get("samples", [])
            if all(s["labels"].get(k) == v for k, v in labels.items()))

    model = model_lib.mellum2_tiny(dtype=jnp.float32)
    opt = optax.adam(1e-3)
    window = dict(kind=SLIDING, window="16", rope="default")
    full = dict(kind=FULL, window="0", rope="yarn")
    rule = dict(held="4", top_k="2", rule="route_top_k", groups="1")
    names = ("hvd_attn_layers_traced_total", "hvd_flash_tiles_traced_total",
             "hvd_moe_layers_traced_total")
    before = (read(names[0], **window), read(names[0], **full),
              read(names[1], mask="sliding_window_w16"),
              read(names[1], mask="causal"), read(names[2], **rule))
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
    # three window layers to a full one
    got_window = read(names[0], **window) - before[0]
    got_full = read(names[0], **full) - before[1]
    assert got_full >= 1 and got_window == 3 * got_full
    assert read(names[1], mask="sliding_window_w16") - before[2] > 0
    assert read(names[1], mask="causal") - before[3] > 0
    assert read(names[2], **rule) - before[4] >= 4
