"""``kanana2-8k``'s step lowered and compiled for a described v5e at the
cell's own size: the model through ``init_train_state``'s shapes and
``make_train_step`` as ``run.py`` builds it, the flash kernels compiled by
Mosaic at ``[1, 8192, 32, 192]`` for q and k and ``[1, 8192, 32, 128]`` for
v.  No chip is attached and nothing runs: this counts the step's Mosaic
calls and holds its memory account before a chip call does.  And the
kernels the accepted cells run, body for body what they were before v had
a head size of its own."""

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

LAYERS = 5
PARAMETERS = 424_960_512
CHIP_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def kanana2_step(topo, no_compile_cache):  # noqa: F811
    """The cell's step compiled for one described chip."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell("kanana2-8k")
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


def test_the_step_has_three_kernels_and_five_calls_of_each(kanana2_step):
    """A recomputed layer keeps ``o`` and ``lse``, so each of the five
    layers calls the forward kernel once, dq and dkv once, and nothing else
    of the step is a Mosaic call: what ``flash_ms`` finds by call target
    and ``flash_mla_roofline`` by name are the flash kernels alone."""
    text = kanana2_step.as_text()
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)
    assert {k: calls.count(k) for k in set(calls)} == {
        "hvd_flash_fwd": LAYERS, "hvd_flash_dq": LAYERS,
        "hvd_flash_dkv": LAYERS}
    for scope in ("hvd_mla/", "hvd_mla_q", "hvd_mla_latent",
                  "hvd_dense_mlp", "hvd_moe_route", "hvd_moe_experts",
                  "hvd_moe_shared", "hvd_loss/"):
        assert scope in text, scope
    # the kernels take q and k at 192 and v at 128: nothing is padded
    assert "bf16[1,32,8192,192]" in text and "bf16[1,32,8192,128]" in text
    assert "bf16[1,32,8192,256]" not in text


def test_the_step_fits_one_chip_beside_the_benchmarks_weights(kanana2_step):
    """``hbm_gb`` as a traced run will print it (arguments + temporaries)
    under what was predicted before the first chip call (PERF.md section 6,
    PR 34), and room for the benchmark's float32 weights through the
    checked steps."""
    mem = kanana2_step.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"hbm {hbm} = arguments {mem.argument_size_in_bytes} + "
          f"temporaries {mem.temp_size_in_bytes}")
    assert 7.0e9 < hbm < 9.5e9, hbm
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


@pytest.mark.parametrize("shape,block_q", list(CAUSAL_KERNELS_BEFORE_THE_MASK))
def test_equal_head_sizes_lower_to_the_kernels_they_had(
        one_chip, no_compile_cache, monkeypatch, shape, block_q):  # noqa: F811
    """Where v's head size is q.k's, the three Mosaic bodies are the ones
    ``test_benchmark_sdar_v5e.py`` holds by digest (the parent of PR 30's):
    the causal cells' kernels did not change when v got a width of its own
    (the whole lowered steps of the seven accepted cells compared alike on
    both commits when PR 34 was built: PERF.md section 6)."""
    from horovod_tpu.ops.flash_attention import flash_attention

    blocks = {} if block_q is None else {"block_q": block_q}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    bodies, _ = _bodies(monkeypatch, lambda: jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, **blocks).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))).lower(x, x, x))
    assert len(bodies) == 3
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() \
        == CAUSAL_KERNELS_BEFORE_THE_MASK[shape, block_q]


def test_the_kernels_compile_at_192_and_128(one_chip, no_compile_cache,  # noqa: F811
                                            monkeypatch):
    """The three kernels at the cell's call, q and k ``[1, 8192, 32, 192]``
    and v ``[1, 8192, 32, 128]``, through Mosaic's compiler for the
    described chip: three bodies, and dq / dk come back 192 wide, dv 128."""
    from horovod_tpu.ops.flash_attention import flash_attention

    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    bodies, lowered = _bodies(monkeypatch, lambda: jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))).lower(qk, qk, v))
    assert len(bodies) == 3
    lowered.compile()
    dq, dk, dv = lowered.out_info
    assert dq.shape == dk.shape == (1, 8192, 32, 192)
    assert dv.shape == (1, 8192, 32, 128)
