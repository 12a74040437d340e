"""``qwen3next-8k``'s own parts compiled for a described v5e at their real
shapes, forward and backward (a file of its own: the accepted
``test_benchmark_kernels_v5e.py`` is the benchmark's)."""

import jax
import jax.numpy as jnp
import pytest

from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)

QWEN_TOKENS = 8192


def _qwen_flash(one_chip):
    from horovod_tpu.models.qwen3_next import flash_blocks
    from horovod_tpu.ops.flash_attention import flash_attention

    bshd = jax.ShapeDtypeStruct((1, QWEN_TOKENS, 16, 256), jnp.bfloat16,
                                sharding=one_chip)
    fn = lambda q, k, v: jnp.sum(flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=False,
        **flash_blocks(256)).astype(jnp.float32))
    return jax.jit(jax.grad(fn, argnums=(0, 1, 2))).lower(bshd, bshd, bshd)


def _qwen_scan(one_chip):
    from horovod_tpu.ops.gated_delta import gated_delta_rule

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qk = shape(1, QWEN_TOKENS, 16, 128)
    v = shape(1, QWEN_TOKENS, 32, 128)
    gb = shape(1, QWEN_TOKENS, 32, dtype=jnp.float32)
    fn = lambda q, k, v, g, beta: jnp.sum(  # noqa: E731
        gated_delta_rule(q, k, v, g, beta).astype(jnp.float32))
    return jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, gb, gb)


def _qwen_experts(one_chip):
    from horovod_tpu.parallel.moe import routed_experts

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    experts = {"gate_proj": shape(16, 2048, 512),
               "up_proj": shape(16, 2048, 512),
               "down_proj": shape(16, 512, 2048)}
    fn = lambda x, router, experts: jnp.sum(routed_experts(  # noqa: E731
        x, router, experts, top_k=10).astype(jnp.float32))
    return jax.jit(jax.grad(fn, argnums=(0, 1, 2))).lower(
        shape(QWEN_TOKENS, 2048, dtype=jnp.bfloat16), shape(2048, 512),
        experts)


QWEN_PARTS = {"flash_head256": (_qwen_flash, 3), "gated_delta_scan":
              (_qwen_scan, 0), "routed_experts": (_qwen_experts, 0)}


@pytest.mark.parametrize("part", sorted(QWEN_PARTS))
def test_qwen3next_part_compiles_for_v5e_forward_and_backward(
        part, one_chip, no_compile_cache):
    """Each of the new cell's own parts at its real shapes, gradient and
    all: flash at 16 heads of 256 with the tiles the model passes (three
    Mosaic kernels), the chunked scan and the expert layer in XLA ops (no
    Mosaic call: the step's only ones stay the flash kernels, which is
    what lets the cell report ``flash_ms``)."""
    lower, mosaic_calls = QWEN_PARTS[part]
    compiled = lower(one_chip).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == mosaic_calls
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 * 2 ** 30