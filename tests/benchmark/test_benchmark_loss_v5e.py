"""Compile, for a described ``v5e:2x2``, the language-model head and
``next_token_loss``, forward and backward, at the shapes the benchmark's
cells give them.  No chip is attached and nothing runs: this holds what the
loss's form is for — no gather forward, no scatter backward, no
compiler-made loop re-tiling a zero-filled ``[b, s, V]`` buffer, and no
temporary of that size beside the logits.  The two-line form it replaced
(``log_softmax`` of a slice, ``take_along_axis``) compiled at
``[1, 16384, 50257]`` to a scatter, a gather, two ``while``-fed
``dynamic-update-slice``s and 11.54 GB of temporaries.  The topology is
described inside the accepted ``test_benchmark_kernels_v5e.py``'s fixture
(a file of its own beside it: that one is the benchmark's).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models.gpt import next_token_loss
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)

#: cell: (rows, tokens, vocabulary, width) of its head on one chip
CELL_SHAPES = {
    "gpt2s-16k": (1, 16384, 50257, 768),
    "gpt2s-1k": (8, 1024, 50257, 768),
    "qwen3next-8k": (1, 8192, 18992, 2048),
}
BANNED = {"scatter", "gather", "while", "dynamic-update-slice"}
#: temporaries allowed, in float32 ``[b, s, V]`` arrays: 3.5 GB at 16k
LOGITS_ARRAYS = 1.0625


def _head_and_loss(x, table, ids):
    """As ``models/gpt.py`` ends: bfloat16 product with the tied table,
    logits in float32 for the softmax."""
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    return next_token_loss(logits.astype(jnp.float32), ids)


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_head_and_loss_compile_without_a_scatter_or_a_loop(
        cell, one_chip, no_compile_cache):
    b, s, v, d = CELL_SHAPES[cell]
    step = jax.jit(jax.value_and_grad(_head_and_loss, argnums=(0, 1)))
    compiled = step.lower(
        jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((v, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip),
    ).compile()
    # an instruction reads "%name = <type> opcode(operands)"; a loop's type
    # is a tuple with spaces in it, so go by the opcode and its bracket
    opcodes = set(re.findall(r" ([a-z][a-z\-]*)\(", compiled.as_text()))
    assert {"fusion", "convolution"} <= opcodes, opcodes
    assert not opcodes & BANNED, opcodes & BANNED
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < LOGITS_ARRAYS * 4 * b * s * v, temporaries
