"""What a recomputed decoder layer keeps (``models/qwen3_next.recomputed``):
the residuals the Pallas forward kernels name for their backward kernels
(``ops/flash_attention.FLASH_OUT`` / ``FLASH_LSE``, ``ops/gated_delta.GDN_OUT``
/ ``GDN_STATES`` / ``GDN_INVERSES``) and nothing else.  At toy size on the
CPU mesh, the kernels in interpreter mode: the gradients are those of the
layers kept whole and of a recompute that keeps nothing; the gradient's
program calls each forward kernel once a layer; a caller without a
checkpoint lowers to the program it had; the counter reads the bytes the
benchmark's two cells keep a layer."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from horovod_tpu import metrics
from horovod_tpu.models import gpt, qwen3_next, sdar
from horovod_tpu.models.gpt import next_token_loss
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops import gated_delta as gdn

TOKENS = 64


def _ids(seed, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (1, TOKENS)), jnp.int32)


def _qwen(interval):
    """(model, a sample, loss of a model and its parameters, layers by the
    forward kernel they call); ``interval`` 1 is full attention in both
    layers, 3 the gated delta rule in both, 2 one of each."""
    model = qwen3_next.qwen3_next_tiny(
        num_layers=2, full_attention_interval=interval, dtype=jnp.float32)
    ids = _ids(0)
    full = sum((i + 1) % interval == 0 for i in range(2))
    return model, ids, lambda m, p: next_token_loss(
        m.apply({"params": p}, ids), ids), {"flash": full, "scan": 2 - full}


def _sdar():
    model = sdar.sdar_tiny(dtype=jnp.float32, qk_norm_init=2.0)
    rng = np.random.default_rng(1)
    batch = (_ids(1, 255),
             jnp.asarray(rng.integers(4096, 65537, (1, TOKENS // 4)),
                         jnp.int32),
             jnp.asarray(rng.integers(0, 65536, (1, TOKENS)), jnp.int32))
    return model, batch, lambda m, p: sdar.block_diffusion_loss(
        m.apply({"params": p}, batch), batch), {"flash": 2, "scan": 0}


MODELS = {
    "qwen3next-full": lambda: _qwen(1),
    "qwen3next-linear": lambda: _qwen(3),
    "qwen3next-hybrid": lambda: _qwen(2),
    "sdar": _sdar,
}


def _launcher_calls(jaxpr):
    """Calls of the jitted kernel launchers in a jaxpr's text."""
    text = str(jaxpr)
    return {kernel: len(re.findall(rf"name={name}\b", text))
            for kernel, name in [
                ("fwd", "_fwd_call"), ("flash_dq", "_dq_call"),
                ("flash_dkv", "_dkv_call"), ("scan_bwd", "_bwd_call")]}


def _expected_calls(forward_calls, forward_passes):
    flash_layers, scan_layers = forward_calls["flash"], forward_calls["scan"]
    # both kernels' forward launchers are named ``_fwd_call``
    return {"fwd": forward_passes * (flash_layers + scan_layers),
            "flash_dq": flash_layers, "flash_dkv": flash_layers,
            "scan_bwd": scan_layers}


@pytest.fixture(scope="module", params=sorted(MODELS))
def case(request):
    """``(forward launcher calls a pass, {variant: (loss, gradients, the
    gradient's jaxpr)})`` of one model with its layers recomputed as they
    are (``kept``), kept ``whole`` (``remat=False``) and recomputed as the
    parent did, by ``nn.remat`` with no policy (``nothing``)."""
    model, sample, loss, forward_calls = MODELS[request.param]()
    assert model.remat
    params = model.init(jax.random.PRNGKey(0), sample)["params"]

    def read(m):
        fn = jax.value_and_grad(lambda p: loss(m, p))
        return (*fn(params), jax.make_jaxpr(fn)(params))

    got = {"kept": read(model), "whole": read(model.clone(remat=False))}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(qwen3_next, "recomputed", nn.remat)
        patch.setattr(sdar, "recomputed", nn.remat)
        got["nothing"] = read(model)
    return forward_calls, got


@pytest.mark.parametrize("other", ["whole", "nothing"])
def test_the_gradients_are_those_of_the_other_two_ways(case, other):
    _, got = case
    assert float(got["kept"][0]) == float(got[other][0])
    for a, b in zip(jax.tree_util.tree_leaves(got["kept"][1]),
                    jax.tree_util.tree_leaves(got[other][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    assert min(float(jnp.linalg.norm(g))
               for g in jax.tree_util.tree_leaves(got["kept"][1])) > 0.0


@pytest.mark.parametrize("variant,forward_passes", [
    ("kept", 1), ("whole", 1), ("nothing", 2)])
def test_a_layer_calls_each_forward_kernel_once(case, variant,
                                                forward_passes):
    """As many forward launchers in the gradient's program as with the
    layers kept whole: the recompute's are dead code once the residuals
    are saved.  Without the policy every forward kernel runs in the forward
    pass and again in the recompute."""
    forward_calls, got = case
    assert _launcher_calls(got[variant][2]) == _expected_calls(
        forward_calls, forward_passes)


def test_everything_but_the_kernels_is_recomputed(case):
    """The saved names are the only thing that leaves a layer's forward
    pass beside its input: the projections' products are in the gradient's
    program once more than in that of layers kept whole (the parent's
    recompute has the forward kernels' own products besides)."""
    _, got = case
    products = {k: str(v[2]).count("dot_general") for k, v in got.items()}
    assert products["nothing"] >= products["kept"] > products["whole"]


def _normalised(text):
    """StableHLO without the counter the symbol table appends to private
    functions' names (a ``name`` equation moves it by one)."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1_N", text)


def test_a_caller_without_a_checkpoint_lowers_to_the_program_it_had(
        monkeypatch):
    """``gpt2_small``'s family keeps its layers whole: with the names in
    the forward rule and without them its gradient is the same program."""
    model = gpt.gpt_tiny(dtype=jnp.float32)
    ids = _ids(2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]

    def lowered():
        flash._flash_fn.cache_clear()
        return jax.jit(jax.grad(lambda p: next_token_loss(
            model.apply({"params": p}, ids), ids))).lower(params).as_text()

    with_names = lowered()
    assert "_fwd_call" in with_names
    monkeypatch.setattr(flash, "checkpoint_name", lambda x, name: x)
    without = lowered()
    flash._flash_fn.cache_clear()
    assert _normalised(with_names) == _normalised(without)
    assert flash.FLASH_OUT not in with_names


def _residual_bytes():
    return {s["labels"]["kernel"]: s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_kernel_residual_bytes_traced_total", {}).get(
                    "samples", [])}


@pytest.mark.parametrize("kernel,nbytes", [
    # sdar-bd4-8k: o bfloat16 [1, 32, 16384, 128] + lse float32 [1, 32, 16384]
    ("flash", 134_217_728 + 2_097_152),
    # qwen3next-8k: o bfloat16 [1, 8192, 32 x 128], states float32
    # [1, 32, 128, 128, 128], inverses float32 [1, 16, 128, 64, 128]
    ("gdn_scan", 67_108_864 + 268_435_456 + 67_108_864)])
def test_the_counter_reads_what_a_layer_of_the_benchmarks_cells_keeps(
        monkeypatch, kernel, nbytes):
    monkeypatch.setattr(metrics.registry, "enabled", True)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    if kernel == "flash":
        x = shape(1, 16384, 32, 128)
        fn, args = (lambda q, k, v: flash.flash_attention(
            q, k, v, mask=flash.block_diffusion_mask(4, 8192), interpret=True),
            (x, x, x))
    else:
        qk, v = shape(1, 8192, 16, 128), shape(1, 8192, 32, 128)
        gb = shape(1, 8192, 32, dtype=jnp.float32)
        fn, args = (lambda *a: gdn.gated_delta_rule(*a, interpret=True),
                    (qk, qk, v, gb, gb))
    before = _residual_bytes().get(kernel, 0)
    jax.make_jaxpr(fn)(*args)  # the plain call keeps nothing
    assert _residual_bytes().get(kernel, 0) == before
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        fn(*a).astype(jnp.float32)), argnums=(0, 1, 2)))(*args)
    assert _residual_bytes()[kernel] - before == nbytes
