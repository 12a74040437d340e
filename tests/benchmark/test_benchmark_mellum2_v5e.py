"""``mellum2-16k``'s step lowered and compiled for a described v5e at the
cell's own size: the model through ``init_train_state``'s shapes and
``make_train_step`` as ``run.py`` builds it, the flash kernels compiled by
Mosaic at ``[1, 16384, 32, 128]`` under the sliding-window mask (three
layers) and the causal one (the fourth).  No chip is attached and nothing
runs: this counts the step's Mosaic calls by kind of layer and holds its
memory account before a chip call does.  And the kernels the accepted cells
run, body for body what they were before the mask had a third case."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)
from test_benchmark_sdar_v5e import CAUSAL_KERNELS_BEFORE_THE_MASK

LAYERS = 4
PARAMETERS = 340_349_184
CHIP_BYTES = 16 * 2 ** 30
#: the Mosaic bodies (printed without locations) of the block-diffusion
#: call ``sdar-bd4-8k`` makes, ``[1, 16384, 32, 128]`` under
#: ``block_diffusion_mask(4, 8192)``, as the parent of this PR lowers them
#: (read there by this file's ``_bodies``): the third case of ``Mask`` added
#: nothing to the second's program
BLOCK_DIFFUSION_KERNELS_BEFORE_THE_WINDOW = \
    "796b8f67ef9034389fae452c20100aace2f72f80742519642b184b364521a7f3"


@pytest.fixture(scope="module")
def mellum2_step(topo, no_compile_cache):  # noqa: F811
    """The cell's step compiled for one described chip."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell("mellum2-16k")
    cfg, mix, adapter = cell.cfg, cell.mix, cell.adapter
    assert cfg["num_hidden_layers"] == LAYERS
    hvd.shutdown()
    try:
        # the state's shapes from a world of host devices: a described chip
        # holds no array
        hvd.init(devices=jax.devices("cpu")[:1])
        prog = adapter.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
        assert sum(x.size for x in jax.tree_util.tree_leaves(
            state.params)) == PARAMETERS
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
        return jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"](arrays)).compile()
    finally:
        hvd.shutdown()


def test_the_step_calls_each_kernel_once_a_layer_by_kind(mellum2_step):
    """A recomputed layer keeps ``o`` and ``lse``, so each of the four
    layers calls the forward kernel once, dq and dkv once: nine calls with
    ``hvd_attn_window`` on their path and three with ``hvd_attn_full``,
    which is how ``flash_swa_roofline`` and ``flash_full_roofline`` tell
    them apart; nothing else of the step is a Mosaic call."""
    text = mellum2_step.as_text()
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)
    assert {k: calls.count(k) for k in set(calls)} == {
        "hvd_flash_fwd": LAYERS, "hvd_flash_dq": LAYERS,
        "hvd_flash_dkv": LAYERS}
    lines = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for kind, layers in (("hvd_attn_window", 3), ("hvd_attn_full", 1)):
        mine = [line for line in lines if f"/hvd_attn/{kind}/" in line]
        assert len(mine) == 3 * layers, kind
        for kernel in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
            assert sum(f"/{kernel}/" in line for line in mine) == layers
    for scope in ("hvd_rotary_tables", "hvd_attn_qkv", "hvd_attn_out",
                  "hvd_flash_layout", "hvd_moe_route", "hvd_moe_experts",
                  "hvd_head", "hvd_loss/"):
        assert scope in text, scope
    # the kernels take q, k and v at the q heads' number: nothing is padded
    assert "bf16[1,32,16384,128]" in text


def test_the_step_fits_one_chip_beside_the_benchmarks_weights(mellum2_step):
    """``hbm_gb`` as a traced run will print it (arguments + temporaries):
    9.217 GB as predicted before the first chip call (PERF.md section 6,
    PR 38), over the 4 GB a new cell has to fill, and room for the
    benchmark's float32 weights through the checked steps."""
    mem = mellum2_step.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"hbm {hbm} = arguments {mem.argument_size_in_bytes} + "
          f"temporaries {mem.temp_size_in_bytes}")
    assert 8.5e9 < hbm < 10.0e9, hbm
    assert hbm > 0.25 * CHIP_BYTES
    assert hbm + 4 * PARAMETERS < 0.75 * CHIP_BYTES


def _bodies(monkeypatch, lower_it):
    """``(the Mosaic bodies ``lower_it()`` lowers, printed without
    locations; what it returned)``."""
    from jax._src import tpu_custom_call

    bodies = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        bodies.append(module.operation.get_asm(enable_debug_info=False))
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    return bodies, lower_it()


def _grads(mask_kw):
    from horovod_tpu.ops.flash_attention import flash_attention

    return jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=False, **mask_kw).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))


@pytest.mark.parametrize("shape,block_q", list(CAUSAL_KERNELS_BEFORE_THE_MASK))
def test_the_causal_kernels_are_the_ones_they_were(
        one_chip, no_compile_cache, monkeypatch, shape, block_q):  # noqa: F811
    """The three Mosaic bodies of a causal call are the ones
    ``test_benchmark_sdar_v5e.py`` holds by digest (the parent of PR 30's):
    the third case of ``Mask`` is decided at trace time, so the causal
    cells' kernels hold no select and no branch of it."""
    blocks = {} if block_q is None else {"block_q": block_q}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    bodies, _ = _bodies(monkeypatch, lambda: _grads(
        dict(causal=True, **blocks)).lower(x, x, x))
    assert len(bodies) == 3
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() \
        == CAUSAL_KERNELS_BEFORE_THE_MASK[shape, block_q]


def test_the_block_diffusion_kernels_are_the_ones_they_were(
        one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    from horovod_tpu.ops.flash_attention import block_diffusion_mask

    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    bodies, _ = _bodies(monkeypatch, lambda: _grads(
        dict(mask=block_diffusion_mask(4, 8192))).lower(x, x, x))
    assert len(bodies) == 3
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() \
        == BLOCK_DIFFUSION_KERNELS_BEFORE_THE_WINDOW


def test_the_window_kernels_compile_at_the_cells_shape(
        one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    """The three kernels under ``sliding_window_mask(1024)`` at
    ``[1, 16384, 32, 128]`` through Mosaic's compiler for the described
    chip: three bodies of their own (the causal ones hold no window), each
    with the two compares of the window's two edges."""
    from horovod_tpu.ops.flash_attention import sliding_window_mask

    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    bodies, lowered = _bodies(monkeypatch, lambda: _grads(
        dict(mask=sliding_window_mask(1024))).lower(x, x, x))
    window = list(bodies)
    assert len(window) == 3
    lowered.compile()
    assert [i.shape for i in lowered.out_info] == [(1, 16384, 32, 128)] * 3
    causal, _ = _bodies(monkeypatch, lambda: _grads(
        dict(causal=True)).lower(x, x, x))
    assert len(causal) == 3 and set(causal).isdisjoint(window)
