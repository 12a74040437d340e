"""The five grouped-query decoders (``sdar``, ``mellum2``, ``lfm2``,
``qwen3_next``, ``nemotron_h``) hand the flash kernels k and v at their own
head count.  At toy size, float32, on the CPU: the logits and every
parameter's gradient equal those of the form the models had (k and v
repeated to the q heads, then a call at equal head counts), within the
tolerances the models' own tests hold them to against the references; and
``recompute_parts`` reckons ``hvd_flash_k`` / ``hvd_flash_v`` at the kv
heads' columns."""

import jax
import jax.numpy as jnp
import pytest

import test_lfm2
import test_mellum2
import test_nemotron_h
import test_qwen3_next
import test_sdar
from horovod_tpu.models.gpt import next_token_loss
from horovod_tpu.ops import flash_attention as fa

# model: (its test module, whose toy configuration, adapter and reference
# this file borrows; how many of the toy's layers are attention layers)
MODELS = {
    "sdar": (test_sdar, 3),
    "mellum2": (test_mellum2, 4),
    "lfm2": (test_lfm2, 2),
    "qwen3_next": (test_qwen3_next, 2),
    "nemotron_h": (test_nemotron_h, 1),
}


def _program(name, kv_heads):
    """``(cfg, model, seeded parameters, batch, loss of (logits, batch))`` of
    the model's toy with ``kv_heads`` kv heads under its four q heads."""
    module, _ = MODELS[name]
    cfg = dict(module.CFG, num_key_value_heads=kv_heads)
    model = module.adapter.program(cfg, module.MIX)["model"]
    params = module.common.unflatten(
        module.ref.seeded_weights(cfg, 2 ** 31 + 5))
    if name == "sdar":
        batch = module._batch(0)
        return cfg, model, params, batch, \
            module.model_lib.block_diffusion_loss
    rows = module.MIX["arrays"][0]["shape"][0]
    ids = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (2, rows), 0, cfg["vocab_size"]), jnp.int32)
    return cfg, model, params, ids, next_token_loss


def _logits_and_grads(model, params, batch, loss):
    def f(p):
        logits = model.apply({"params": p}, batch)
        return loss(logits, batch), logits

    # one program a side: op by op the toys take three times as long
    (_, logits), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(params)
    return logits, grads


# the toys' own two kv heads under four q heads, and one under four in the
# model whose budget now keeps them
@pytest.mark.parametrize("name,kv_heads", [
    *((name, 2) for name in sorted(MODELS)), ("sdar", 1)])
def test_the_model_is_what_it_was_with_k_and_v_repeated(
        name, kv_heads, monkeypatch):
    module, _ = MODELS[name]
    cfg, model, params, batch, loss = _program(name, kv_heads)
    seen = []

    def repeated(q, k, v, **kw):
        """The parent's call: equal head counts, k and v repeated outside."""
        seen.append((q.shape[2], k.shape[2], v.shape[2]))
        group = q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        return fa.flash_attention(q, k, v, **kw)

    logits, grads = _logits_and_grads(model, params, batch, loss)
    with monkeypatch.context() as patch:
        patch.setattr(module.model_lib, "flash_attention", repeated)
        was_logits, was_grads = _logits_and_grads(model, params, batch, loss)
    # the model hands the kernels its kv heads, not q's
    assert seen and set(seen) == {(4, kv_heads, kv_heads)}
    assert float(jnp.max(jnp.abs(logits - was_logits))) < 1e-5
    flatten = module.common.flatten
    grads, was_grads = flatten(grads), flatten(was_grads)
    assert set(grads) == set(was_grads)
    largest = max(float(jnp.linalg.norm(w)) for w in was_grads.values())
    for leaf, was in was_grads.items():
        # against the leaf's own norm, or a thousandth of the largest leaf's
        # where its own is smaller (test_qwen3_next.py's floor)
        scale = max(float(jnp.linalg.norm(was)), 1e-3 * largest)
        assert float(jnp.linalg.norm(grads[leaf] - was)) < 1e-5 * scale, leaf


@pytest.mark.parametrize("name", sorted(MODELS))
def test_recompute_parts_reckons_k_and_v_at_the_kv_heads(name):
    """``hvd_flash_k`` / ``hvd_flash_v`` over the attention layers: rows x
    kv heads x head size x item size each; q at q's heads as it was."""
    _, attention_layers = MODELS[name]
    cfg, model, _, batch, _ = _program(name, 2)
    b, s = (batch[0] if name == "sdar" else batch).shape
    s *= 2 if name == "sdar" else 1      # both copies are rows of a call
    parts, _ = model.recompute_parts(b, s)
    head, size = model.head_dim, jnp.dtype(model.dtype).itemsize
    assert (model.num_heads, model.num_kv_heads) == (4, 2)
    assert parts[fa.FLASH_K] == parts[fa.FLASH_V] \
        == attention_layers * b * s * 2 * head * size
    assert parts[fa.FLASH_Q] == attention_layers * b * s * 4 * head * size
