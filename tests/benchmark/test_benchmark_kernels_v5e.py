"""Compile, for a described ``v5e:2x2``, the three Mosaic flash kernels at
the shapes the benchmark's GPT cells time: 8 x 1024 and 1 x 16384 tokens,
12 heads of 64, bfloat16, causal.  No chip is attached and nothing runs:
this catches what interpret mode cannot (tiling, VMEM limits) before a
chip call does.  The topology is described inside a fixture, never while a
module is imported (on-chip-measurement guide, section 2), and all of
these tests live in this one file.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: (rows, tokens) of the gpt2s-1k / gpt2s-1k-dp4 and the gpt2s-16k cells
CELL_SHAPES = {"1k": (8, 1024), "16k": (1, 16384)}
HEADS, HEAD_DIM = 12, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shapes(cell, one_chip):
    b, s = CELL_SHAPES[cell]
    bhsd = jax.ShapeDtypeStruct((b, HEADS, s, HEAD_DIM), jnp.bfloat16,
                                sharding=one_chip)
    rows = jax.ShapeDtypeStruct((b, HEADS, s, 1), jnp.float32,
                                sharding=one_chip)
    return bhsd, rows


def _forward(cell, one_chip):
    from horovod_tpu.ops.flash_attention import flash_attention

    b, s = CELL_SHAPES[cell]
    bshd = jax.ShapeDtypeStruct((b, s, HEADS, HEAD_DIM), jnp.bfloat16,
                                sharding=one_chip)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True,  # noqa: E731
                                         interpret=False)
    return jax.jit(fn).lower(bshd, bshd, bshd)


def _backward(which):
    def lower(cell, one_chip):
        from horovod_tpu.ops import flash_attention as fa

        kernel = {"dq": fa.mha_bwd_dq, "dkv": fa.mha_bwd_dkv}[which]
        bhsd, rows = _shapes(cell, one_chip)
        fn = lambda q, k, v, do, lse, delta: kernel(  # noqa: E731
            q, k, v, do, lse, delta, 0, 0, causal=True,
            scale=HEAD_DIM ** -0.5, interpret=False)
        return jax.jit(fn).lower(bhsd, bhsd, bhsd, bhsd, rows, rows)

    return lower


KERNELS = {"forward": _forward, "dq": _backward("dq"),
           "dkv": _backward("dkv")}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_flash_kernel_compiles_for_v5e(kernel, cell, one_chip,
                                       no_compile_cache):
    compiled = KERNELS[kernel](cell, one_chip).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        f"{kernel} at {cell}: no Mosaic kernel in the compiled module"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2 ** 30


def test_allreduce_readers_find_every_allreduce_of_the_four_chip_step(
        topo, no_compile_cache):
    """The data-parallel step compiled for the four described chips, at
    toy widths: every instruction of the module whose opcode is
    ``all-reduce`` is one the trace reduction would count (``lax.psum``
    names most of them ``%psum.N``: a reader that went by the name read
    78.8 of gpt2s-1k-dp4's 497.8 MB), and the bytes ``allreduce_mb`` adds
    up are the gradient's and the loss's."""
    import benchmark_tiny
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.configs import gpt2_small
    from benchmarks.harness import trace
    from benchmarks.layer_metrics import allreduce_mb

    cfg, mix = benchmark_tiny.GPT_TINY, benchmark_tiny.SEQ_TINY
    hvd.shutdown()
    try:
        # the state's shapes from a world of four host devices: a described
        # chip holds no array
        hvd.init(devices=jax.devices("cpu")[:4])
        prog = gpt2_small.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
        hvd.shutdown()
        hvd.init(devices=list(topo.devices))
        whole = NamedSharding(core.mesh(), P())
        rows = NamedSharding(core.mesh(), P(core.AXIS))
        prog = gpt2_small.program(cfg, mix)
        step = make_train_step(
            apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
            optimizer=prog["optimizer"])
        ids = jax.ShapeDtypeStruct(
            (4 * mix["rows_per_chip"], mix["items_per_row"]), jnp.int32,
            sharding=rows)
        text = jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"]((ids,))).compile().as_text()
    finally:
        hvd.shutdown()
    ops = [trace.Op(line.strip(), 0.0, 0.0, "") for line in
           text.splitlines() if " all-reduce" in line and " = " in line]
    assert ops and all(trace.is_allreduce(op) for op in ops)
    params = sum(math.prod(a.shape) for a in
                 jax.tree_util.tree_leaves(state.params))
    assert sum(allreduce_mb.result_bytes(op.name) for op in ops
               if not trace.is_allreduce_done(op)) == 4 * (params + 1)
