"""The Qwen3-Next family (``models/qwen3_next.py``) against the benchmark's
plain reference at toy size, float32 on both sides so that routing agrees:
parameter names and shapes, logits, loss and every gradient leaf over two
periods of the layer pattern; the pieces of the mixers on their own."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.configs import qwen3_next_80b_a3b as adapter  # noqa: E402
from benchmarks.references import common, qwen3_next as ref  # noqa: E402
from horovod_tpu.models import qwen3_next as model_lib  # noqa: E402
from horovod_tpu.models.gpt import next_token_loss  # noqa: E402

#: two periods of (DeltaNet, DeltaNet, DeltaNet, full): both mixers repeat
CFG = {
    "num_hidden_layers": 8, "full_attention_interval": 4, "hidden_size": 32,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rope_theta": 10000,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_experts": 4,
    "router_num_experts": 16, "first_expert": 4, "num_experts_per_tok": 3,
    "rms_norm_eps": 1e-06, "vocab_size": 96, "initializer_range": 0.02,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 1e-4, "remat": "decoder_layer",
}
MIX = {"arrays": [{"shape": [40]}]}


@pytest.fixture(scope="module")
def setup():
    model = adapter.program(CFG, MIX)["model"]
    params = common.unflatten(ref.seeded_weights(CFG, 2 ** 31 + 5))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG["vocab_size"], (2, 40)), jnp.int32)
    return model, params, ids


def test_reference_and_program_name_the_same_leaves(setup):
    model, params, ids = setup
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    assert {k: v.shape for k, v in common.flatten(shapes).items()} \
        == {k: v.shape for k, v in common.flatten(params).items()} \
        == ref.param_shapes(CFG)


def test_loss_and_every_gradient_leaf_match_the_reference(setup):
    model, params, ids = setup
    want, want_grad = jax.value_and_grad(ref.loss_fn(CFG))(params, ids)
    got, got_grad = jax.value_and_grad(lambda p: next_token_loss(
        model.apply({"params": p}, ids), ids))(params)
    assert abs(float(got) - float(want)) < 1e-5
    diff = common.leaf_diff_norms(common.flatten(got_grad),
                                  common.flatten(want_grad))
    norm = common.leaf_norms(common.flatten(want_grad))
    assert len(norm) == len(ref.param_shapes(CFG))
    assert min(float(v) for v in norm.values()) > 0.0, \
        min(norm, key=lambda k: float(norm[k]))
    # a leaf's difference against its own norm, or against a thousandth of
    # the largest leaf's where its own is smaller (A_log and dt_bias get
    # gradients of 1e-7, at float32's rounding of the sums they come from)
    floor = 1e-3 * max(float(v) for v in norm.values())
    for k in diff:
        assert float(diff[k]) <= 1e-3 * max(float(norm[k]), floor), k


def test_logits_match_a_reference_forward(setup):
    """The reference has no logits of its own (its head is fused into the
    loss), so its layers are driven here and the head applied plainly."""
    model, params, ids = setup
    got = model.apply({"params": params}, ids)
    x = params["embed_tokens"]["embedding"][ids]
    for i in range(CFG["num_hidden_layers"]):
        x = ref._layer(x, params[f"layers_{i}"], CFG,
                       ref.is_full_attention(CFG, i), lambda t: t)
    want = ref._norm(x, params["norm"]["weight"], 1e-6) @ params["lm_head"]
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 96)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_remat_changes_nothing_but_what_is_kept(setup):
    model, params, ids = setup
    plain = model.clone(remat=False)

    def loss(m):
        return jax.value_and_grad(lambda p: next_token_loss(
            m.apply({"params": p}, ids), ids))(params)

    (a, ga), (b, gb) = loss(model), loss(plain)
    assert float(a) == pytest.approx(float(b), abs=1e-6)
    worst = max(float(v) for v in common.leaf_diff_norms(
        common.flatten(ga), common.flatten(gb)).values())
    assert worst < 1e-6


def test_rotary_embedding_turns_pairs_of_halves_and_passes_the_rest():
    x = jnp.ones((1, 3, 1, 8))
    cos, sin = model_lib.rotary_tables(jnp.arange(3), 4, 100.0)
    out = model_lib.apply_rotary(x, cos, sin)
    np.testing.assert_allclose(out[0, 0, 0], 1.0)          # position 0
    np.testing.assert_allclose(out[..., 4:], 1.0)          # not rotated
    # dims (0, 2) are one pair at angle p, dims (1, 3) one at p / 10
    np.testing.assert_allclose(out[0, 1, 0, 0], np.cos(1) - np.sin(1),
                               rtol=1e-6)
    np.testing.assert_allclose(out[0, 1, 0, 2], np.cos(1) + np.sin(1),
                               rtol=1e-6)
    np.testing.assert_allclose(out[0, 2, 0, 1], np.cos(.2) - np.sin(.2),
                               rtol=1e-6)


def test_causal_convolution_sees_no_later_token():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 10, 3)),
                    jnp.float32)
    kernel = jnp.asarray(np.random.default_rng(1).normal(size=(4, 3)),
                         jnp.float32)
    y = model_lib.causal_depthwise_conv(x, kernel)
    want = np.zeros((10, 3))
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += np.asarray(kernel)[j] * np.asarray(x)[0, t - 3 + j]
    np.testing.assert_allclose(y[0], want, atol=1e-6)
    later = x.at[0, 7].add(1.0)
    np.testing.assert_allclose(
        model_lib.causal_depthwise_conv(later, kernel)[0, :7], y[0, :7])


def test_tiny_preset_trains_through_make_train_step(hvd_init):
    import optax

    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)

    model = model_lib.qwen3_next_tiny(dtype=jnp.float32)
    opt = optax.adam(1e-3)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 32)),
                      jnp.int32)
    state = init_train_state(model, opt, ids[:1])
    step = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=next_token_loss, optimizer=opt)
    losses = []
    for _ in range(4):
        state, loss = step(state, shard_batch(ids), shard_batch(ids))
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
