"""The flash kernels on the flattened grid of PR 43, through Mosaic's
compiler for a described v5e at the shapes ``sdar-bd4-8k``, ``gpt2s-16k``
and ``kanana2-8k`` call them with: the streamed axis of each grid is as
long as ``grid_census`` counts live steps, and no longer.  The window
kernels at ``mellum2-16k``'s shape are body for body the parent's.  And the
digest of the block-diffusion bodies as that PR leaves them, beside the
pinned one it turned red by design
(``test_benchmark_mellum2_v5e.BLOCK_DIFFUSION_KERNELS_BEFORE_THE_WINDOW``):
what the next ``benchmark`` PR re-points it to (the causal bodies pinned in
``test_benchmark_sdar_v5e.CAUSAL_KERNELS_BEFORE_THE_MASK`` are of calls over
one block of keys, whose rectangle has no idle step: they keep it, and the
digests hold).  No chip is attached and
nothing runs (a file of its own: only a ``benchmark`` PR edits one that is
there)."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)
from test_benchmark_mellum2_v5e import _bodies, _grads

# cell: ([b, s, h, q's head size], v's head size, mask) of its call
CELLS = {
    "sdar-bd4-8k": ((1, 16384, 32, 128), 128, ("block_diffusion", 4, 8192)),
    "gpt2s-16k": ((1, 16384, 12, 64), 64, ("causal",)),
    "kanana2-8k": ((1, 8192, 32, 192), 128, ("causal",)),
}

#: sha256 of the three Mosaic bodies (forward, dq, dkv; printed without
#: locations) of the gradient of ``mellum2-16k``'s window call, ``[1, 16384,
#: 32, 128]`` under ``sliding_window_mask(1024)``, as the parent of PR 43
#: lowers them for this chip.
WINDOW_KERNELS_BEFORE_THE_TABLE = \
    "2c3dc42cdfa49c7d55d79746ecb5400a3b5bf31c8adf87f21058672edb26828b"

#: The same of ``sdar-bd4-8k``'s call, ``[1, 16384, 32, 128]`` under
#: ``block_diffusion_mask(4, 8192)``, whose digest PR 43 changed by design:
#: the one the accepted file pins, and the one since.
BLOCK_DIFFUSION_KERNELS = (
    "796b8f67ef9034389fae452c20100aace2f72f80742519642b184b364521a7f3",
    "6d472cf941552c1113a74699b3479133b53a8fefd373430980e8852c0331825b")


def _mask(kind, *args):
    from horovod_tpu.ops import flash_attention as fa

    return {"causal": lambda: fa.CAUSAL,
            "block_diffusion": fa.block_diffusion_mask,
            "sliding_window": fa.sliding_window_mask}[kind](*args)


def _lowered(monkeypatch, one_chip, shape, dv, mask, **blocks):  # noqa: F811
    """``(the Mosaic bodies of the call's gradient, printed without
    locations; the gradient lowered for the described chip)``."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((*shape[:3], dv), jnp.bfloat16,
                             sharding=one_chip)
    bodies, lowered = _bodies(monkeypatch, lambda: _grads(
        dict(mask=mask, **blocks)).lower(x, x, v))
    assert len(bodies) == 3
    return bodies, lowered


def _digest(bodies):
    return hashlib.sha256("\n".join(bodies).encode()).hexdigest()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernels_compile_on_a_grid_of_the_live_pairs(
        cell, one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    from horovod_tpu.ops import flash_attention as fa

    shape, dv, mask = CELLS[cell]
    mask = _mask(*mask)
    (b, s, h, d) = shape
    bodies, lowered = _lowered(monkeypatch, one_chip, shape, dv, mask)
    steps = fa.grid_census(s, s, *fa.default_blocks(d, mask), mask)
    for kernel, body in zip(("fwd", "dq", "dkv"), bodies):
        grid = [int(n) for n in re.search(
            r"iteration_bounds = array<i64: ([\d, ]+)>", body).group(1).split(
                ",")]
        assert grid == [b, h, steps[kernel]["live"]], kernel
        assert steps[kernel]["launched"] == steps[kernel]["live"]
        # the table behind the two offsets, in scalar memory
        assert (f"memref<{2 + 3 * grid[2]}xi32, #tpu.memory_space<smem>>"
                in body)
    assert steps["fwd"]["live"] < 128 and steps["dkv"]["live"] < 128
    lowered.compile()
    assert [i.shape for i in lowered.out_info] == [shape, shape,
                                                   (b, s, h, dv)]


def test_the_window_kernels_are_the_parents(
        one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    """A sliding window keeps PR 39's fitted grid and its launchers: the
    three bodies at ``mellum2-16k``'s shape, by digest."""
    bodies, _ = _lowered(monkeypatch, one_chip, (1, 16384, 32, 128), 128,
                         _mask("sliding_window", 1024))
    assert all(body.count("#tpu.dimension_semantics<parallel>") == 3
               for body in bodies)
    assert _digest(bodies) == WINDOW_KERNELS_BEFORE_THE_TABLE


def test_the_block_diffusion_kernels_since_the_table(
        one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    from test_benchmark_mellum2_v5e import (
        BLOCK_DIFFUSION_KERNELS_BEFORE_THE_WINDOW)

    before, since = BLOCK_DIFFUSION_KERNELS
    assert before == BLOCK_DIFFUSION_KERNELS_BEFORE_THE_WINDOW
    shape, dv, mask = CELLS["sdar-bd4-8k"]
    bodies, _ = _lowered(monkeypatch, one_chip, shape, dv, _mask(*mask))
    assert _digest(bodies) == since != before
