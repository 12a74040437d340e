"""``lfm2-8k-b2``'s step lowered and compiled for a described v5e at the
cell's own size: the model through ``init_train_state``'s shapes and
``make_train_step`` as ``run.py`` builds it.  No chip is attached and
nothing runs: this counts the step's Mosaic calls (three an attention
layer, none under ``hvd_sconv``: the gates and taps are XLA's), holds every
part of the convolution operator under its scope, first run, recompute and
transposes, and reads the step's memory account and what the recomputed
layers keep before a chip call does."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, topo)

CELL = "lfm2-8k-b2"
#: {layers held: (parameters, convolution layers, attention layers)}
LAYERS = {7: (647_819_520, 5, 2), 5: (469_284_992, 4, 1)}
CHIP_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def lfm2_step(topo, no_compile_cache):  # noqa: F811
    """``(layers, parameters, what the recomputed layers kept, the cell's
    step compiled for one described chip)``."""
    import horovod_tpu as hvd
    from horovod_tpu import core, metrics
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell(CELL)
    cfg, mix, adapter = cell.cfg, cell.mix, cell.adapter
    enabled = metrics.registry.enabled
    hvd.shutdown()
    try:
        # the state's shapes from a world of host devices: a described chip
        # holds no array
        hvd.init(devices=jax.devices("cpu")[:1])
        prog = adapter.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
        parameters = sum(x.size for x in jax.tree_util.tree_leaves(
            state.params))
        hvd.shutdown()
        hvd.init(devices=[topo.devices[0]])
        whole = NamedSharding(core.mesh(), P())
        rows = NamedSharding(core.mesh(), P(core.AXIS))
        prog = adapter.program(cfg, mix)
        step = make_train_step(
            apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
            optimizer=prog["optimizer"])
        arrays = tuple(jax.ShapeDtypeStruct(
            (mix["rows_per_chip"], *a["shape"]), jnp.dtype(a["dtype"]),
            sharding=rows) for a in mix["arrays"])
        metrics.registry.enabled = True
        before = _kept(metrics)
        compiled = jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"](arrays)).compile()
        kept = {k: v - before.get(k, 0) for k, v in _kept(metrics).items()}
        return cfg["num_hidden_layers"], parameters, kept, compiled
    finally:
        metrics.registry.enabled = enabled
        hvd.shutdown()


def _kept(metrics) -> dict:
    return {s["labels"]["name"]: s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_recompute_kept_bytes_traced_total", {}).get(
                    "samples", [])}


def test_three_mosaic_calls_an_attention_layer_and_none_under_hvd_sconv(
        lfm2_step):
    """Each attention layer calls each flash kernel once (a recomputed
    layer keeps ``o`` and ``lse``) and nothing else of the step is a Mosaic
    call: the convolution operator is XLA ops, every one of them under
    ``hvd_sconv`` and one of its three parts."""
    layers, parameters, _, step = lfm2_step
    want, n_conv, n_attn = LAYERS[layers]
    assert parameters == want
    text = step.as_text()
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)
    assert {k: calls.count(k) for k in set(calls)} == {
        "hvd_flash_fwd": n_attn, "hvd_flash_dq": n_attn,
        "hvd_flash_dkv": n_attn}
    mosaic = [line for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    assert len(mosaic) == 3 * n_attn
    assert not any("hvd_sconv" in line for line in mosaic)
    assert all("/hvd_attn/" in line for line in mosaic)
    paths = re.findall(r'op_name="([^"]+)"', text)
    sconv = [p for p in paths if "/hvd_sconv/" in p]
    assert len({re.search(r"layers_(\d+)", p).group(1) for p in sconv
                if "layers_" in p}) == n_conv
    # every op of the operator is under exactly one of its parts
    parts = ("hvd_sconv_in", "hvd_sconv_conv", "hvd_sconv_out")
    for p in sconv:
        assert sum(f"/{part}/" in p for part in parts) == 1, p
    for part in parts:
        mine = [p for p in sconv if f"/{part}/" in p]
        assert any("transpose(jvp(" in p for p in mine), part
        assert any("transpose(" not in p for p in mine), part
    # both products of the operator are XLA's, under their parts
    assert any(p.endswith("dot_general") and "/hvd_sconv_in/" in p
               for p in sconv)
    assert any(p.endswith("dot_general") and "/hvd_sconv_out/" in p
               for p in sconv)
    assert not any(p.endswith("dot_general") and "/hvd_sconv_conv/" in p
                   for p in sconv)
    for scope in ("hvd_attn_qkv", "hvd_attn_out", "hvd_flash_layout",
                  "hvd_dense_mlp", "hvd_moe_route", "hvd_moe_experts",
                  "hvd_head", "hvd_loss/"):
        assert scope in text, scope
    # no shared expert in this model
    assert "hvd_moe_shared" not in text


def test_the_step_holds_fill_and_fits_beside_the_benchmarks_weights(
        lfm2_step):
    """``recompute.FILL``: what a recomputed layer keeps beyond the
    kernels' residuals comes out of 0.73 of the chip less what the step
    holds anyway.  At seven layers that budget is spent before a name is
    kept (16 B a parameter, the layers' inputs, the flash kernels'
    residuals and the logits are 12.58 GB of 12.54): nothing is kept, every
    part of a layer runs a second time, and the step with the benchmark's
    float32 weights beside it through the checked steps stays under the
    15.5 GB ISSUE 45's seven-or-five rule reads on the chip."""
    from horovod_tpu.models import recompute

    layers, parameters, kept, step = lfm2_step
    mem = step.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"hbm {hbm} = arguments {mem.argument_size_in_bytes} + "
          f"temporaries {mem.temp_size_in_bytes}; kept {kept}")
    assert mem.argument_size_in_bytes >= 12 * parameters
    assert hbm > 0.25 * CHIP_BYTES and hbm > 4e9
    assert hbm + 4 * parameters < 15.5e9
    names = {k: v for k, v in kept.items() if k != "skipped" and v > 0}
    if layers == 7:
        assert not names and kept["skipped"] > 3e9
        assert 16 * parameters > 0.8 * recompute.FILL * CHIP_BYTES
    # what is kept is inside the budget FILL leaves: kept or not, the step
    # holds no more than FILL of the chip beyond its temporaries' slack
    assert sum(names.values()) <= recompute.FILL * CHIP_BYTES \
        - 16 * parameters
    text = step.as_text()
    again = [line for line in text.splitlines()
             if "rematted_computation" in line]
    assert any("/hvd_sconv_in/" in line for line in again)
    assert any("/hvd_sconv_conv/" in line for line in again)
    assert not [line for line in again
                if 'custom_call_target="tpu_custom_call"' in line]
