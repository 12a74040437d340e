"""The block-diffusion decoder (``models/sdar.py``) against the benchmark's
plain reference at toy size, float32 on both sides so that routing agrees:
parameter names and shapes, logits, loss and every gradient leaf; the two
leak tests on the layers with the two copies fed apart; the masked-token
loss against its definition; the expert layer's eight shares; a batch of
three arrays a row through ``make_train_step``."""

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

from benchmarks.configs import sdar_30b_a3b_chat as adapter  # noqa: E402
from benchmarks.references import common, sdar as ref  # noqa: E402
from horovod_tpu import metrics  # noqa: E402
from horovod_tpu.models import sdar as model_lib  # noqa: E402
from horovod_tpu.models.gpt import weighted_token_loss  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from horovod_tpu.parallel.moe import routed_experts  # noqa: E402

CFG = {
    "num_hidden_layers": 3, "hidden_size": 32, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "rope_theta": 10000,
    "moe_intermediate_size": 16, "num_experts": 4, "router_num_experts": 16,
    "first_expert": 4, "num_experts_per_tok": 3, "rms_norm_eps": 1e-06,
    "vocab_size": 96, "data_vocab_size": 95, "mask_token_id": 95,
    "block_length": 4, "initializer_range": 0.02, "qk_norm_init": 2.0,
    "moe_group_rows": 48, "moe_capacity_factor": 1.0,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 1e-4, "remat": "decoder_layer",
}
LENGTH, BLOCK = 48, 4
MIX = {"arrays": [{"shape": [LENGTH]}]}


def _batch(seed, rows=2, length=LENGTH, vocab=CFG["data_vocab_size"]):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, vocab, (rows, length)), jnp.int32),
            jnp.asarray(rng.integers(4096, 65537, (rows, length // BLOCK)),
                        jnp.int32),
            jnp.asarray(rng.integers(0, 65536, (rows, length)), jnp.int32))


@pytest.fixture(scope="module")
def setup():
    model = adapter.program(CFG, MIX)["model"]
    params = common.unflatten(ref.seeded_weights(CFG, 2 ** 31 + 5))
    return model, params, _batch(0)


def test_reference_and_program_name_the_same_leaves(setup):
    model, params, batch = setup
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            batch)["params"]
    assert {k: v.shape for k, v in common.flatten(shapes).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()} \
        == ref.param_shapes(CFG)
    # seeded weights give every leaf a first gradient: norm weights one,
    # the heads' q and k norms at the configuration's qk_norm_init
    layer = params["layers_0"]
    assert float(layer["input_layernorm"]["weight"][0]) == 1
    assert float(layer["self_attn"]["q_norm"]["weight"][0]) \
        == float(layer["self_attn"]["k_norm"]["weight"][0]) \
        == CFG["qk_norm_init"] == 2.0


def test_logits_match_the_reference(setup):
    model, params, batch = setup
    got = model.apply({"params": params}, batch)
    assert got.shape == (2, LENGTH, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.logits_fn(CFG)(params, *batch)),
        atol=2e-5, rtol=2e-4)


def test_loss_and_every_gradient_leaf_match_the_reference(setup):
    model, params, batch = setup
    want, want_grad = jax.value_and_grad(ref.loss_fn(CFG))(params, *batch)
    got, got_grad = jax.value_and_grad(
        lambda p: model_lib.block_diffusion_loss(
            model.apply({"params": p}, batch), batch))(params)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    want_grad, got_grad = common.flatten(want_grad), common.flatten(got_grad)
    for name, w in want_grad.items():
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, name
        assert float(jnp.linalg.norm(got_grad[name] - w)) < 2e-4 * scale, name


# -- nothing leaks: the two copies fed apart ---------------------------------


def _copies(seed=3):
    rng = np.random.default_rng(seed)
    clean = rng.integers(0, 95, (1, LENGTH))
    noised = np.where(rng.random((1, LENGTH)) < 0.5, 95, clean)
    return noised, clean


def _decode(model, params, noised, clean, bounded=False):
    """The two copies as the caller made them.  ``bounded``: with the
    experts' load bound, which couples a group's rows through the order in
    which an expert takes them (an earlier row's pick can push a later
    row's out); without it the layers couple rows through attention
    alone."""
    rows = jnp.asarray(np.concatenate([noised, clean], axis=1), jnp.int32)
    if not bounded:
        model = model.clone(moe_capacity_factor=None)
    return np.asarray(model.apply({"params": params}, rows,
                                  method="decode"))


@pytest.mark.parametrize("block", [0, 5, 11])
def test_a_noised_token_moves_no_logit_outside_its_block(setup, block):
    """Nothing sees another block's noise: not the later blocks' noised
    rows, not the clean rows that feed them."""
    model, params, _ = setup
    noised, clean = _copies()
    before = _decode(model, params, noised, clean)
    at = block * BLOCK + 1
    noised[0, at] = (noised[0, at] + 7) % 95
    after = _decode(model, params, noised, clean)
    inside = slice(block * BLOCK, (block + 1) * BLOCK)
    assert np.abs(after[0, inside] - before[0, inside]).max() > 1e-4
    outside = np.ones(LENGTH, bool)
    outside[inside] = False
    np.testing.assert_array_equal(after[0, outside], before[0, outside])


@pytest.mark.parametrize("bounded", [False, True])
@pytest.mark.parametrize("block", [0, 5, 11])
def test_clean_tokens_of_a_block_and_later_move_no_logit_of_it(
        setup, block, bounded):
    """A noised row sees the clean blocks *before* its own: the clean copy
    of its own block and of every later one is hidden from it (or the
    model would read the answer).  That holds under the experts' load
    bound too: an expert takes a group's rows in row order, so a row is
    never pushed out by a later one, and the noised rows come first."""
    model, params, _ = setup
    noised, clean = _copies()
    before = _decode(model, params, noised, clean, bounded)
    clean[0, block * BLOCK:] = (clean[0, block * BLOCK:] + 11) % 95
    after = _decode(model, params, noised, clean, bounded)
    upto = (block + 1) * BLOCK
    np.testing.assert_array_equal(after[0, :upto], before[0, :upto])
    if upto < LENGTH:
        # the later blocks do read what changed before them
        assert np.abs(after[0, upto:] - before[0, upto:]).max() > 1e-4


def test_call_noises_doubles_and_decodes(setup):
    """``__call__`` is ``decode`` of ``[noised ; clean]`` made from the
    draws and the levels; a level of 65536 masks every token of its block,
    one of 0 none."""
    model, params, (ids, level, draw) = setup
    level = level.at[:, 0].set(65536).at[:, 1].set(0)
    masked = np.asarray(model_lib.noised_tokens(ids, level, draw))
    assert masked[:, :BLOCK].all() and not masked[:, BLOCK:2 * BLOCK].any()
    np.testing.assert_array_equal(masked, np.asarray(
        draw < np.repeat(np.asarray(level), BLOCK, axis=1)))
    noised = np.where(masked, 95, np.asarray(ids))
    np.testing.assert_array_equal(
        np.asarray(model.apply({"params": params}, (ids, level, draw))),
        _decode(model, params, noised, np.asarray(ids), bounded=True))
    with pytest.raises(ValueError, match="do not cover"):
        model.apply({"params": params}, (ids, level[:, :-1], draw))


# -- the loss -----------------------------------------------------------------


def test_block_diffusion_loss_is_its_definition_by_log_softmax(rng):
    ids, level, draw = _batch(7, rows=3)
    logits = jnp.asarray(rng.normal(size=(3, LENGTH, 96)), jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(log_probs, ids[..., None], axis=-1)[..., 0]
    t = np.repeat(np.asarray(level) / 65536.0, BLOCK, axis=1)
    m = np.asarray(draw) < np.repeat(np.asarray(level), BLOCK, axis=1)
    want = float(np.sum(-np.asarray(picked) * m / t) / (3 * LENGTH))
    got = model_lib.block_diffusion_loss(logits, (ids, level, draw))
    assert abs(float(got) - want) < 1e-5 * abs(want)
    # and its gradient is (softmax - one hot) * weight, on masked rows only
    grad = np.asarray(jax.grad(
        lambda x: model_lib.block_diffusion_loss(x, (ids, level, draw)))(
            logits))
    one_hot = np.asarray(jax.nn.one_hot(ids, 96))
    np.testing.assert_allclose(
        grad, (np.asarray(jax.nn.softmax(logits, -1)) - one_hot)
        * (m / t)[..., None] / (3 * LENGTH), atol=1e-7)
    assert not grad[~m].any()


def test_weighted_token_loss_keeps_no_log_probabilities_and_no_gather():
    """PR 27's property: the compiled loss and its gradient hold no
    ``[b, s, V]`` array beside the logits and their cotangent, and pick the
    target's logit without gather or scatter."""
    logits = jax.ShapeDtypeStruct((2, 64, 512), jnp.float32)
    args = (jax.ShapeDtypeStruct((2, 64), jnp.int32),
            jax.ShapeDtypeStruct((2, 64), jnp.float32))
    text = jax.jit(jax.value_and_grad(weighted_token_loss)).lower(
        logits, *args).as_text()
    assert "gather" not in text and "scatter" not in text
    assert "dynamic_slice" not in text and "dynamic_update_slice" not in text


# -- the expert layer's shares --------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rng):
    """Eight chips hold two of sixteen experts each: the parts their
    ``routed_experts`` give, and the reference's, add up to what the
    reference gives for the whole layer (no shared expert to count once)."""
    d, f, experts, top_k = 32, 16, 16, 3
    cfg = dict(CFG, num_experts=experts, router_num_experts=experts,
               first_expert=0, hidden_size=d, moe_intermediate_size=f,
               num_experts_per_tok=top_k, moe_capacity_factor=None)
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x = mk(2, 2 * LENGTH, d)
    p = {"gate": mk(d, experts), "experts_gate_proj": mk(experts, d, f),
         "experts_up_proj": mk(experts, d, f),
         "experts_down_proj": mk(experts, f, d)}
    identity = lambda a: a  # noqa: E731
    whole = np.asarray(ref.moe(x, p, cfg, identity))
    parts_ref = parts_program = 0.0
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        mine = {k: (v if k == "gate" else v[held]) for k, v in p.items()}
        parts_ref = parts_ref + np.asarray(ref.moe(
            x, mine, dict(cfg, num_experts=2, first_expert=2 * share),
            identity))
        parts_program = parts_program + np.asarray(routed_experts(
            x.reshape(-1, d), p["gate"],
            {k[len("experts_"):]: v for k, v in mine.items() if k != "gate"},
            top_k=top_k, first_expert=2 * share)).reshape(x.shape)
    np.testing.assert_allclose(parts_ref, whole, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(parts_program, whole, atol=1e-6, rtol=1e-5)


def _bounded_layer(x, router, p, *, top_k, first_expert, capacity):
    """The layer by the reference's pieces, dense, one group."""
    held = p["gate_proj"].shape[0]
    gates = ref.gate_weights(x, router, top_k)[
        :, first_expert:first_expert + held]
    if capacity is not None:
        gates = ref.bounded(gates, x.shape[0], capacity)
    hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", x, p["gate_proj"])) \
        * jnp.einsum("nd,edf->enf", x, p["up_proj"])
    return jnp.einsum("ne,enf,efd->nd", gates, hidden, p["down_proj"])


@pytest.mark.parametrize("capacity", [1, 2, 5, 9, 40, None])
def test_a_capacity_drops_an_experts_picks_past_its_first(
        rng, monkeypatch, capacity):
    """``routed_experts(capacity=c)`` against the reference's layer under
    the same bound: the result and the gradients to the tokens, the router
    and the experts, from a bound that leaves an expert one row to one no
    expert reaches (72 picks an expert when even) and none."""
    monkeypatch.setattr(moe, "TILE", 8)
    n, d, f, experts, held, top_k = 192, 32, 16, 8, 4, 3
    mk = lambda *s: jnp.asarray(0.2 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x, router, cot = mk(n, d), mk(d, experts), mk(n, d)
    p = {"gate_proj": mk(held, d, f), "up_proj": mk(held, d, f),
         "down_proj": mk(held, f, d)}

    def run(layer, **kw):
        return jax.value_and_grad(
            lambda x, router, p: jnp.sum(cot * layer(
                x, router, p, top_k=top_k, first_expert=2, **kw)),
            argnums=(0, 1, 2))(x, router, p)

    want = run(_bounded_layer, capacity=capacity)
    got = run(routed_experts, capacity=capacity)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5,
                                   rtol=1e-5)
    # the bound binds: under 72 the layer is not the unbounded one
    unbounded = run(routed_experts)[0]
    assert (abs(float(got[0]) - float(unbounded)) > 1e-4) \
        == (capacity is not None and capacity < 72)


def test_the_references_bound_keeps_the_first_picks_of_each_group(rng):
    """``bounded`` against a count by hand: of every ``group`` rows an
    expert keeps its first ``capacity`` picks, in row order."""
    gates = np.where(rng.random((24, 5)) < 0.5, rng.random((24, 5)),
                     0.0).astype(np.float32)
    got = np.asarray(ref.bounded(jnp.asarray(gates), 8, 2))
    want = np.zeros_like(gates)
    for start in range(0, 24, 8):
        for e in range(5):
            rows = [i for i in range(start, start + 8) if gates[i, e] > 0]
            for i in rows[:2]:
                want[i, e] = gates[i, e]
    np.testing.assert_array_equal(got, want)
    assert (got != gates).any()


def test_the_model_groups_its_rows_and_seeds_its_own_temperature(setup):
    """The toy configuration bounds the load (4 groups of 48 rows, 9 picks
    an expert a group) and the bound drops something: the logits are not
    the unbounded model's; rows that are not whole groups are refused; the
    model's own initial q / k norm weights are ``qk_norm_init``."""
    model, params, batch = setup
    assert (model.moe_group_rows, model.moe_capacity_factor) == (48, 1.0)
    free = model.clone(moe_capacity_factor=None)
    assert float(jnp.max(jnp.abs(
        model.apply({"params": params}, batch)
        - free.apply({"params": params}, batch)))) > 1e-6
    with pytest.raises(ValueError, match="whole groups"):
        model.clone(moe_group_rows=80).apply({"params": params}, batch)
    own = model.init(jax.random.PRNGKey(0), batch)["params"]["layers_0"]
    assert float(own["self_attn"]["k_norm"]["weight"][3]) \
        == model.qk_norm_init == CFG["qk_norm_init"]
    assert float(own["input_layernorm"]["weight"][3]) == 1


# -- three arrays a row through the step builder ---------------------------------


def test_a_tuple_batch_goes_through_make_train_step(hvd_init, monkeypatch):
    """Every leaf of the batch is sharded on dim 0, the step trains, the
    rows are counted once in ``hvd_samples_total`` and the layers in
    ``hvd_bd_layers_traced_total``."""
    import horovod_tpu as hvd
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read(name, **labels):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get(name, {}).get("samples", [])
            if all(s["labels"].get(k) == v for k, v in labels.items()))

    model = model_lib.sdar_tiny(dtype=jnp.float32, num_layers=1)
    opt = optax.adam(1e-3)
    sample = tuple(a[:1] for a in _batch(1, vocab=255))
    state = init_train_state(model, opt, sample)
    step = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=model_lib.block_diffusion_loss, optimizer=opt)
    batch = shard_batch(tuple(np.asarray(a) for a in _batch(
        2, rows=hvd.size(), vocab=255)))
    assert all(len(a.addressable_shards) == hvd.size()
               and a.addressable_shards[0].data.shape[0] == 1
               for a in batch)
    params = jax.device_get(state.params)      # the step donates its state
    samples = read("hvd_samples_total")
    layers = read("hvd_bd_layers_traced_total", block="4")
    losses = []
    for _ in range(3):
        state, loss = step(state, batch, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[2] < losses[0]
    assert read("hvd_samples_total") - samples == 3 * hvd.size()
    assert read("hvd_bd_layers_traced_total", block="4") - layers >= 1
    # the loss the step reports is the mean over the ranks' rows
    want = model_lib.block_diffusion_loss
    assert abs(losses[0] - float(np.mean([
        want(model.apply({"params": params},
                         tuple(np.asarray(a)[r:r + 1] for a in batch)),
             tuple(np.asarray(a)[r:r + 1] for a in batch))
        for r in range(hvd.size())]))) < 1e-4
