"""``sdar-bd4-8k``'s step lowered and compiled for a described v5e at the
cell's own size: the model through ``init_train_state``'s shapes and
``make_train_step`` as ``run.py`` builds it, the flash kernels compiled by
Mosaic (``interpret=False``: the mesh's device is a TPU) under the
block-diffusion mask at ``[1, 16384, 32, 128]``.  No chip is attached and
nothing runs: this counts the step's Mosaic calls and holds its memory
account before a chip call does (a file of its own: the accepted
``test_benchmark_kernels_v5e.py`` is the benchmark's; its fixtures describe
the topology inside a fixture, never while a module is imported)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)

LAYERS = 4


@pytest.fixture(scope="module")
def sdar_step(topo, no_compile_cache):  # noqa: F811
    """The cell's step compiled for one described chip."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell("sdar-bd4-8k")
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
        lowered = jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"](arrays))
        return lowered.compile()
    finally:
        hvd.shutdown()


def test_the_steps_mosaic_calls_are_the_flash_kernels_under_their_scopes(
        sdar_step):
    """Nothing of the step but the three flash kernels is a Mosaic call:
    what ``flash_ms`` finds by call target and ``flash_bd_roofline`` by name
    are the flash kernels alone, and the scopes the cell's readers go by
    are in the compiled text.  (How often a layer calls each kernel, once
    since PR 33 kept the forward's output across the recompute, is
    ``test_benchmark_recompute_v5e.py``'s.)"""
    text = sdar_step.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    by_kernel = {k: sum(f"hvd_flash_{k}" in line for line in calls)
                 for k in ("fwd", "dq", "dkv")}
    assert sum(by_kernel.values()) == len(calls) and min(
        by_kernel.values()) >= LAYERS
    for scope in ("hvd_bd_noise", "hvd_bd_head_rows", "hvd_moe_route",
                  "hvd_moe_experts", "hvd_loss/"):
        assert scope in text, scope


def test_the_step_fits_one_chip_beside_the_benchmarks_weights(sdar_step):
    """``hbm_gb`` as a traced run will print it (arguments + temporaries),
    and room for the benchmark's float32 weights through the checked
    steps."""
    mem = sdar_step.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 6.5e9 < hbm < 8.0e9, hbm
    assert hbm + 4 * 456_346_624 < 0.75 * 16 * 2 ** 30


#: sha256 of the three Mosaic bodies (forward, dq, dkv; printed without
#: locations) of a causal call's gradient as the parent of PR 30 lowered
#: them for this chip: ``[1, 2048, 12, 64]`` as ``gpt2_small`` calls the
#: kernels and ``[1, 2048, 2, 256]`` at ``block_q`` 512 as
#: ``qwen3_next_80b_a3b`` does.  PR 30 gave the kernels a ``Mask``; causal is
#: one case of it and its programs did not change.  A PR that means to
#: change the causal kernels brings its chip readings and new digests.
CAUSAL_KERNELS_BEFORE_THE_MASK = {
    ((1, 2048, 12, 64), None):
        "729ad7783ab8670197379667c88f8694bdc922285690bccf76a204e3d89ddba6",
    ((1, 2048, 2, 256), 512):
        "b6a6cd7efe258e1dd36af9b2ccc26d9bb34204619c05a5d026f0579a8d0cacda",
}


@pytest.mark.parametrize("shape,block_q", list(CAUSAL_KERNELS_BEFORE_THE_MASK))
def test_the_causal_callers_kernels_are_the_parents(
        one_chip, no_compile_cache, monkeypatch, shape, block_q):  # noqa: F811
    """The kernels the four causal cells run, body for body what they were
    before the mask description (the whole steps of ``gpt2s-16k`` and
    ``qwen3next-8k`` compared alike on both commits when PR 30 was built:
    PERF.md section 6)."""
    import hashlib

    from jax._src import tpu_custom_call

    from horovod_tpu.ops.flash_attention import flash_attention

    bodies = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        bodies.append(module.operation.get_asm(enable_debug_info=False))
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    blocks = {} if block_q is None else {"block_q": block_q}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, **blocks).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))).lower(x, x, x)
    assert len(bodies) == 3
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() \
        == CAUSAL_KERNELS_BEFORE_THE_MASK[shape, block_q]
