"""The Nemotron-H decoder (``models/nemotron_h.py``) against the
benchmark's plain reference at toy size, float32 on both sides so that
routing agrees: parameter names and shapes, logits, three training steps'
losses, every leaf's first gradient and update, with the experts' load
bound biting and without; causality; the seeded ``dt_bias``, ``A_log`` and
``D`` by hand; the gated grouped norm by hand; the blocks' kinds from the
published pattern; an expert block's sixteen shares; the model through
``make_train_step``."""

import functools
import json
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

from benchmarks.configs import nemotron3_nano_30b_a3b as adapter  # noqa: E402
from benchmarks.references import common, nemotron_h as ref  # noqa: E402
from horovod_tpu import metrics  # noqa: E402
from horovod_tpu.models import nemotron_h as model_lib  # noqa: E402
from horovod_tpu.models.gpt import next_token_loss  # noqa: E402
from horovod_tpu.ops.ssd import ssd_recurrence  # noqa: E402
from horovod_tpu.parallel.moe import (route_sigmoid_top_k,  # noqa: E402
                                      routed_experts)

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
CFG = {
    "hybrid_override_pattern": PUBLISHED, "num_hidden_layers": 7,
    "hidden_size": 32, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "n_routed_experts": 4, "router_num_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "norm_eps": 1e-05, "vocab_size": 96, "initializer_range": 0.02,
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
    # a block is one norm and one mixer, of its kind
    assert set(params["layers_0"]) == {"norm", "mixer"}
    assert set(params["layers_0"]["mixer"]) == {
        "in_proj", "conv1d", "conv_bias", "dt_bias", "A_log", "D", "norm",
        "out_proj"}
    assert set(params["layers_1"]["mixer"]) == {
        "gate", "experts_up_proj", "experts_down_proj",
        "shared_experts_up_proj", "shared_experts_down_proj"}
    assert set(params["layers_5"]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                                "o_proj"}
    # no gate among the experts' matrices; the only biases are the
    # convolution's and dt's (the selection bias is no parameter)
    assert not any("gate_proj" in name for name in common.flatten(params))
    assert {name.rsplit("/", 1)[1] for name in common.flatten(params)
            if "bias" in name} == {"conv_bias", "dt_bias"}


@pytest.mark.parametrize("blocks,kinds", [(9, "MEMEM*EME"), (7, "MEMEM*E")])
def test_the_blocks_kinds_are_the_first_of_the_published_pattern(blocks,
                                                                 kinds):
    cfg = dict(CFG, num_hidden_layers=blocks)
    assert ref.kinds(cfg) == kinds
    model = adapter.program(cfg, MIX)["model"]
    assert model.kinds() == tuple(kinds)
    assert model_lib.PATTERN == PUBLISHED and len(PUBLISHED) == 52
    assert [PUBLISHED.count(k) for k in "ME*"] == [23, 23, 6]
    with pytest.raises(ValueError, match="pattern"):
        ref.kinds(dict(cfg, num_hidden_layers=53))
    with pytest.raises(ValueError, match="pattern"):
        model.clone(pattern="MEX").kinds()
    # the committed configuration holds the pattern whole and cuts by count
    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           "nemotron3_nano_30b_a3b.json")) as fh:
        real = json.load(fh)
    assert real["hybrid_override_pattern"] == PUBLISHED
    assert ref.kinds(real) in ("MEMEM*EME", "MEMEM*E")


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


@pytest.mark.parametrize("blocks", [7, 9])
def test_every_gradient_leaf_matches_the_reference_leaf_by_leaf(blocks):
    cfg, model, params = _setup(num_hidden_layers=blocks)
    ids = _ids(3)
    want = common.flatten(jax.grad(ref.loss_fn(cfg))(params, ids))
    got = common.flatten(jax.grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params))
    assert set(got) == set(want)
    for name, w in want.items():
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(got[name] - w)) < 1e-5 * scale, name


@pytest.mark.parametrize("factor", [None, 0.4])
@pytest.mark.parametrize("at", [1, 17, 40])
def test_no_later_token_moves_an_earlier_logit(at, factor):
    """A causal convolution, a recurrence, causal attention, and a load
    bound that takes an expert's rows in row order: a row is never pushed
    out by a later one."""
    _, model, params = _setup(moe_capacity_factor=factor)
    ids = np.array(_ids(5, rows=1))
    before = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    ids[0, at:] = (ids[0, at:] + 7) % CFG["vocab_size"]
    after = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_array_equal(after[0, :at], before[0, :at])
    assert np.abs(after[0, at:] - before[0, at:]).max() > 1e-5


# -- the seeded weights ---------------------------------------------------------


def test_the_state_space_leaves_are_seeded_by_the_sources_rules(setup):
    """``A_log = log(1..H)``, ``D = 1``, ``softplus(dt_bias)`` inside
    ``[time_step_min, time_step_max]`` and another draw a block and a seed;
    the convolution's bias zero, norm weights one; in the reference's
    weights and in the program's own ``init``."""
    cfg, model, params = setup
    own = model.init(jax.random.PRNGKey(3), _ids(0))["params"]
    heads = cfg["mamba_num_heads"]
    for tree in (params, own):
        for i in (0, 2, 4):
            m = tree[f"layers_{i}"]["mixer"]
            np.testing.assert_allclose(
                np.asarray(m["A_log"]), np.log(np.arange(1, heads + 1)),
                rtol=1e-6)
            assert np.asarray(m["D"]).tolist() == [1.0] * heads
            dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
            assert (dt >= cfg["time_step_min"] * (1 - 1e-4)).all()
            assert (dt <= cfg["time_step_max"] * (1 + 1e-4)).all()
            assert not np.asarray(m["conv_bias"]).any()
            assert (np.asarray(m["norm"]) == 1).all()
            assert (np.asarray(tree[f"layers_{i}"]["norm"]["weight"])
                    == 1).all()
    assert not np.allclose(params["layers_0"]["mixer"]["dt_bias"],
                           params["layers_2"]["mixer"]["dt_bias"])
    other = ref.seeded_weights(cfg, SEED + 1)
    assert not np.allclose(params["layers_0"]["mixer"]["dt_bias"],
                           other["layers_0/mixer/dt_bias"])
    np.testing.assert_array_equal(
        np.asarray(ref.seeded_weights(cfg, SEED)["layers_0/mixer/dt_bias"]),
        np.asarray(params["layers_0"]["mixer"]["dt_bias"]))


def test_dt_bias_is_the_inverse_softplus_by_hand():
    """``dt + log(-expm1(-dt))`` undoes softplus: at dt = 0.05 it is
    log(e^0.05 - 1) = -2.9702; a draw under the floor is raised to it."""
    cfg = dict(CFG, time_step_min=0.05, time_step_max=0.05)
    bias = np.asarray(ref.seeded_dt_bias(cfg, jax.random.PRNGKey(0), 4))
    np.testing.assert_allclose(bias, math.log(math.expm1(0.05)), rtol=1e-5)
    floored = dict(CFG, time_step_min=1e-5, time_step_max=1e-5,
                   time_step_floor=1e-4)
    np.testing.assert_allclose(
        np.asarray(jax.nn.softplus(ref.seeded_dt_bias(
            floored, jax.random.PRNGKey(0), 4))), 1e-4, rtol=1e-4)
    own = model_lib.dt_bias_init(0.05, 0.05, 1e-4)(jax.random.PRNGKey(1),
                                                   (4,))
    np.testing.assert_allclose(np.asarray(own), math.log(math.expm1(0.05)),
                               rtol=1e-5)


# -- the parts of the mixer -----------------------------------------------------


def test_the_mixer_is_the_equations_by_hand(setup, rng):
    """One state-space mixer against the definition written out with
    ``ssd_recurrence``: the split ``[z | xBC | dt]``, the convolution over
    x, B and C together with its bias, the gate before the norm, the norm a
    group of columns."""
    cfg, model, params = setup
    p = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jnp.asarray(rng.normal(size=a.shape),
                                        jnp.float32),
        params["layers_0"]["mixer"])
    u = jnp.asarray(rng.normal(size=(2, LENGTH, cfg["hidden_size"])),
                    jnp.float32)
    heads, hp, g, n = 4, 8, 2, 16
    inner = heads * hp
    mixer = model_lib.Mamba2Mixer(
        **model.mixers()[model_lib.MAMBA], dtype=jnp.float32)
    got = mixer.apply({"params": p}, u)
    zxbcdt = np.asarray(u) @ np.asarray(p["in_proj"]["kernel"])
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-heads],
                  zxbcdt[..., -heads:])
    assert xbc.shape[-1] == inner + 2 * g * n
    padded = np.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    conv = sum(padded[:, j:j + LENGTH] * np.asarray(p["conv1d"])[j]
               for j in range(4)) + np.asarray(p["conv_bias"])
    xbc = conv / (1 + np.exp(-conv))
    y = np.asarray(ssd_recurrence(
        jnp.asarray(xbc[..., :inner].reshape(2, LENGTH, heads, hp)),
        jax.nn.softplus(jnp.asarray(dt) + p["dt_bias"]),
        -jnp.exp(p["A_log"]),
        jnp.asarray(xbc[..., inner:inner + g * n].reshape(2, LENGTH, g, n)),
        jnp.asarray(xbc[..., inner + g * n:].reshape(2, LENGTH, g, n)),
        p["D"])).reshape(2, LENGTH, inner)
    y = y * (z / (1 + np.exp(-z)))
    groups = y.reshape(2, LENGTH, g, inner // g)
    groups = groups / np.sqrt(np.mean(groups ** 2, axis=-1, keepdims=True)
                              + cfg["norm_eps"])
    want = (groups.reshape(2, LENGTH, inner) * np.asarray(p["norm"])) \
        @ np.asarray(p["out_proj"]["kernel"])
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-4)
    # and the reference's mixer is the same
    np.testing.assert_allclose(
        np.asarray(ref._mamba(u, p, cfg, lambda a: a)), want, atol=2e-5,
        rtol=2e-4)


def test_reference_recurrence_in_blocks_is_the_recurrence(rng):
    x = jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(2, 40, 4)),
                                     jnp.float32))
    rate = -jnp.exp(jnp.asarray(rng.normal(size=(4,)), jnp.float32))
    b, c = (jnp.asarray(rng.normal(size=(2, 40, 4, 16)), jnp.float32)
            for _ in range(2))
    skip = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    whole = ssd_recurrence(x, dt, rate, b, c, skip)
    for block in (40, 16, 7):
        np.testing.assert_allclose(
            np.asarray(ref.state_recurrence(x, dt, rate, b, c, skip,
                                            lambda a: a, block)),
            np.asarray(whole), atol=1e-5, rtol=1e-5)


def test_reference_attention_in_blocks_is_the_softmax_unblocked(rng):
    from horovod_tpu.ops.flash_attention import softmax_attention

    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
               for _ in range(3))
    whole = softmax_attention(q, k, v, causal=True)
    for head_block, query_block in ((4, 32), (2, 8), (1, 16)):
        np.testing.assert_allclose(
            np.asarray(ref.causal_attention(q, k, v, lambda a: a, head_block,
                                            query_block)),
            np.asarray(whole), atol=2e-6)
    with pytest.raises(ValueError, match="whole blocks"):
        ref.causal_attention(q, k, v, lambda a: a, 3, 8)


# -- the expert block's shares ---------------------------------------------------


def test_the_sixteen_shares_of_an_expert_block_add_up_to_the_uncut_block(
        rng):
    """Sixteen chips hold two of thirty-two experts each: the parts their
    ``routed_experts`` give under the sigmoid rule with relu^2 experts, and
    the reference's, with the shared expert counted once, add up to what
    the reference gives for the whole block, without capacity."""
    d, f, experts, top_k, shares = 32, 16, 32, 6, 16
    cfg = dict(CFG, n_routed_experts=experts, router_num_experts=experts,
               first_expert=0, hidden_size=d, moe_intermediate_size=f,
               num_experts_per_tok=top_k, moe_capacity_factor=None)
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x = mk(2, LENGTH, d)
    p = {"gate": mk(d, experts), "experts_up_proj": mk(experts, d, f),
         "experts_down_proj": mk(experts, f, d),
         "shared_experts_up_proj": {"kernel": mk(d, 2 * f)},
         "shared_experts_down_proj": {"kernel": mk(2 * f, d)}}
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
            top_k=top_k, first_expert=share * held, route=route,
            form="relu2")).reshape(x.shape)
    np.testing.assert_allclose(parts_ref, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(parts_program, whole, atol=2e-6, rtol=1e-5)


def test_the_model_groups_its_rows_and_seeds_every_matrix_alike(setup):
    cfg, model, params = setup
    assert (model.moe_group_rows, model.moe_capacity_factor) == (48, 1.25)
    with pytest.raises(ValueError, match="whole groups"):
        model.clone(moe_group_rows=80).apply({"params": params}, _ids(0))
    own = model.init(jax.random.PRNGKey(0), _ids(0))["params"]
    theirs = common.unflatten(ref.seeded_weights(cfg, SEED))
    # normal(0, initializer_range) in the program's init and in the
    # benchmark's weights: no matrix has a scale of its own
    for tree in (own, theirs):
        attn, moe = tree["layers_5"]["mixer"], tree["layers_1"]["mixer"]
        for leaf in (attn["q_proj"]["kernel"], attn["o_proj"]["kernel"],
                     moe["experts_up_proj"], moe["experts_down_proj"],
                     moe["shared_experts_down_proj"]["kernel"],
                     moe["gate"],
                     tree["layers_0"]["mixer"]["out_proj"]["kernel"]):
            assert abs(float(jnp.std(leaf)) - 0.02) < 0.004


# -- through the step builder ----------------------------------------------------


def test_the_model_trains_through_make_train_step(hvd_init, monkeypatch):
    """``init_train_state`` / ``make_train_step`` take it as they take the
    other language models; the state-space mixers are counted by their
    sizes and the expert blocks by their routing rule and their form."""
    import horovod_tpu as hvd
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read(name, **labels):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get(name, {}).get("samples", [])
            if all(s["labels"].get(k) == v for k, v in labels.items()))

    model = model_lib.nemotron_h_tiny(dtype=jnp.float32)
    opt = optax.adam(1e-3)
    ssm = dict(heads="4", head_dim="8", state="16", groups="2", chunk="16")
    rule = dict(held="4", top_k="2", rule="route_sigmoid_top_k+relu2",
                groups="1")
    before = (read("hvd_ssm_layers_traced_total", **ssm),
              read("hvd_moe_layers_traced_total", **rule),
              read("hvd_moe_layers_traced_total",
                   rule="route_sigmoid_top_k"))
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
    assert read("hvd_ssm_layers_traced_total", **ssm) - before[0] >= 3
    assert read("hvd_moe_layers_traced_total", **rule) - before[1] >= 3
    # the gated form's label reads as it did: this model adds nothing to it
    assert read("hvd_moe_layers_traced_total",
                rule="route_sigmoid_top_k") == before[2]
