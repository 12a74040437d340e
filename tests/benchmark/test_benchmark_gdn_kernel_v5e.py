"""The gated delta rule's two Pallas kernels compiled for a described v5e at
the shapes ``qwen3next-8k`` gives them (1 x 8192 tokens, 16 key and 32
value heads of 128, bfloat16, float32 ``g`` and ``beta``), gradient and
all, with ``interpret=False``.  No chip is attached and nothing runs: this
holds what the kernels are for — the scan's only device work is the two
Mosaic calls, no ``while`` and no ``triangular_solve`` is left under
``hvd_gdn_scan``, and only the differentiated forward call writes the chunk
states and inverses.  (A file of its own: ``test_benchmark_qwen3_next_v5e.py``
is the benchmark's; its ``gated_delta_scan`` case calls the function without
``interpret=False``, so on the CPU it compiles the interpreter's program
and still finds no Mosaic call: it holds nothing about the kernels.)
"""

import re

import jax
import jax.numpy as jnp
import pytest

from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)

TOKENS, KEY_HEADS, VALUE_HEADS, HEAD = 8192, 16, 32, 128
#: what the differentiated forward call keeps for the backward kernel:
#: ``[b, value heads, chunks of 64, dk, dv]`` float32, 268 MB, and the
#: chunks' inverses, two value heads side by side, 67 MB
STATES = f"f32[1,{VALUE_HEADS},{TOKENS // 64},{HEAD},{HEAD}]"
INVERSES = f"f32[1,{KEY_HEADS},{TOKENS // 64},64,128]"


def _shapes(one_chip):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qk = shape(1, TOKENS, KEY_HEADS, HEAD)
    v = shape(1, TOKENS, VALUE_HEADS, HEAD)
    gb = shape(1, TOKENS, VALUE_HEADS, dtype=jnp.float32)
    return qk, qk, v, gb, gb


def _scan(*args):
    from horovod_tpu.ops.gated_delta import gated_delta_rule

    return gated_delta_rule(*args, interpret=False)


@pytest.fixture(scope="module")
def compiled(one_chip, no_compile_cache):
    """``{"plain": the forward call alone, "grad": the gradient in all five
    arguments}``, compiled."""
    loss = lambda *a: jnp.sum(_scan(*a).astype(jnp.float32))  # noqa: E731
    fns = {"plain": jax.jit(_scan),
           "grad": jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))}
    return {name: fn.lower(*_shapes(one_chip)).compile()
            for name, fn in fns.items()}


def _mosaic_calls(text):
    """Names of the instructions that call a Mosaic kernel."""
    return sorted(re.sub(r"\.\d+$", "", name) for name in re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))


@pytest.mark.parametrize("which,kernels", [
    ("plain", ["hvd_gdn_scan_fwd"]),
    ("grad", ["hvd_gdn_scan_bwd", "hvd_gdn_scan_fwd"])])
def test_the_scans_mosaic_calls_are_its_two_kernels(which, kernels,
                                                    compiled):
    text = compiled[which].as_text()
    assert _mosaic_calls(text) == kernels
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            # the scope the metrics read, outside the kernel's own name
            path = re.search(r'op_name="([^"]*)"', line).group(1)
            assert re.search(r"hvd_gdn_scan[)/]", path), path


@pytest.mark.parametrize("which", ["plain", "grad"])
def test_no_loop_and_no_solve_is_left_under_the_scope(which, compiled):
    opcodes = {m.group(1) for line in compiled[which].as_text().splitlines()
               if "hvd_gdn_scan" in line
               for m in [re.search(r" ([a-z][a-z\-]*)\(", line)] if m}
    assert "custom-call" in opcodes
    assert not opcodes & {"while", "triangular-solve", "scatter", "gather",
                          "dynamic-update-slice"}, opcodes
    assert "triangular" not in compiled[which].as_text()


@pytest.mark.parametrize("kept", [STATES, INVERSES])
def test_only_the_differentiated_forward_keeps_anything(kept, compiled):
    assert kept not in compiled["plain"].as_text()
    assert kept in compiled["grad"].as_text()


@pytest.mark.parametrize("which", ["plain", "grad"])
def test_the_scan_fits_beside_the_step(which, compiled):
    mem = compiled[which].memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8 * 2 ** 30


def test_shapes_the_kernels_cannot_tile_are_named(one_chip):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qk, v = shape(1, 256, 2, 64), shape(1, 256, 4, 64)
    gb = shape(1, 256, 4, dtype=jnp.float32)
    with pytest.raises(ValueError, match="dk 64, dv 64, chunk 64"):
        jax.jit(_scan).lower(qk, qk, v, gb, gb)
