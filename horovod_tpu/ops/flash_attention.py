"""Pallas TPU flash-attention kernels.

The reference framework contains no attention code at all (SURVEY §5:
sequence parallelism "absent"); long-context support is a first-class goal
of the TPU build, and this module is its compute core: a blockwise
online-softmax ("flash") attention kernel family written in Pallas so the
hot loop runs out of VMEM and the q·kᵀ / p·v contractions land on the MXU.

Kernel structure.  A score tile is ``block_q x block_k``.  The streamed
side of each kernel is the innermost *grid* dimension (not a
``fori_loop``), with the accumulators in VMEM scratch that persists across
grid steps, so the Mosaic pipeline overlaps each block's DMA with the
previous block's compute; one grid step streams ``TILES_PER_STEP`` tiles'
worth of it (keys for forward and dq, query rows for dkv).  What a step
does with its block is decided from the offsets, at run time, by scalar
arithmetic, so the local call and ring attention's traced offsets run the
same code:

* the resident rows see none of it (wholly past the diagonal): nothing is
  computed, and the block's index map names the nearest block that is, so
  nothing is fetched either; such a step still costs 0.3-0.5 us, and which
  grid a call runs decides how many there are (below);
* forward and dq take the tiles their rows see at all as *one* block of
  static width (a body for the whole block and one for half of it), so the
  online softmax's statistics move once a grid step and not once a tile;
  the forward has a third body without the mask for blocks its rows see
  whole (the mask is a tenth of its time there; under 1% of dq's and
  dkv's, which mask every causal tile);
* dkv loops over the query tiles that are not skipped.

The logit scale is multiplied into the ``[block, d]`` operand where that
is exact (a power of two) and never onto the s^2 path of the backward
(``ds`` goes unscaled into its product, ``dq``/``dk`` take the scale as
they are written).  dkv computes its scores transposed, keys down the
rows, so that ``p^T do`` and ``ds^T q`` need no transpose of a score tile
and the row statistics arrive as lane-dense rows.  The forward's row sums
stay partial sums a lane until the q block is done.  Every body is code
that Mosaic unrolls and every layer's kernel carries, so the bodies are
few, the resident rows are walked ``ROW_CHUNK`` at a time in a loop, and
the launchers are jitted, which lets a model's layers share one trace and
one lowering.

Which steps the grid has is decided from the call's mask and offsets, and
from nothing else (:func:`grid_census` counts them, the counter
``hvd_flash_grid_steps_traced_total`` records them):

* causal or block diffusion with offsets that are Python ints (every
  model's call): a *flattened grid* ``(batch, heads, pairs)`` over the live
  (resident block, streamed block) pairs, in the resident block's order.
  A table behind the two offsets in the scalar-prefetched operand tells
  the index maps and the body which pair a step is and whether it is the
  resident block's first (zero the accumulators) or last (write the
  outputs): :func:`_pair_table`, :func:`_grid`, :func:`_grid_step`.  No
  step is visited to do nothing;
* a sliding window: the grid fitted to the window (below), with static and
  with traced offsets;
* traced offsets under a causal mask (ring attention's calls), no mask,
  a rectangle without an idle step (a block or two of keys: 1024 rows at
  the default tiles) or more pairs than scalar memory holds
  (``MAX_PAIRS``): the rectangle ``(batch, heads, resident blocks,
  streamed blocks)``.

One body serves all three: what differs is where it reads its block
positions from.

Measured on one TPU v5e ("TPU v5 lite"), PR 25, each kernel alone on the
device's clock at the two shapes the benchmark's GPT cells run, bf16,
causal, in us per computed 512 x 512 tile (fwd / dq / dkv):

====================================  ==================  ==================
kernels                               [8,12,1024,64]      [1,12,16384,64]
====================================  ==================  ==================
before (one 512 x 512 tile a step)    2.28 / 1.89 / 2.51  2.13 / 1.76 / 2.89
  its mask removed                    2.28 / 1.85 / 2.51  2.12 / 1.72 / 2.89
  its statistics removed (forward)    1.46                1.34
  1024 x 1024 tiles                   1.63 / 1.56 / 2.11  1.25 / 1.41 / 2.06
these, 512 x 512, one tile a step     1.77 / 1.73 / 1.91  1.63 / 1.61 / 1.84
these, 512 x 512, four tiles a step   1.43 / 1.27 / 1.65  1.11 / 1.30 / 1.51
these as they are (1024 x 512, four)  1.43 / 1.27 / 1.68  1.03 / 1.24 / 1.53
====================================  ==================  ==================

(the matmuls alone need 0.68 / 1.02 / 1.36 at head_dim 64; the rows
between were taken with every body unrolled, 110 MB of program for the
16k step against 30 MB as they are).  The time was in the ``[block_q,
1]`` statistics and their reductions across lanes, once a tile, and in
dkv's two transposes; the mask cost nothing until those were gone.
PERF.md section 6 has the whole table.

Three public entry points:

* :func:`flash_attention` — full (normalized) local attention with a
  custom VJP whose backward pass is also Pallas kernels.  Drop-in
  ``attention_fn`` for the flax models and the local step of Ulysses.
* :func:`mha_partial` — unnormalized streaming triple ``(o, m, l)`` for one
  q-shard × kv-shard pair with *global-position* causal masking via
  dynamic offsets; this is the per-hop block compute of ring attention
  (the offsets arrive as scalar-prefetch operands, so the ring step can
  pass traced ``lax.axis_index``-derived values).
* :func:`mha_bwd_dq` / :func:`mha_bwd_dkv` — backward blocks with the same
  offset masking, used by the ring attention backward rotation.

All kernels take/return the ``[batch, heads, seq, head_dim]`` layout; the
callers transpose from the model-facing ``[batch, seq, heads, head_dim]``.
k and v come at their own head count ``hk``, q's ``h`` a multiple of it
(grouped-query attention; the group is read from the shapes): forward and
dq, whose grids run over q's heads, read kv head ``i // (h // hk)`` for q
head ``i`` through the kv blocks' index maps; dkv's grid runs over the kv
heads, and a resident block of keys streams the query blocks of every q
head of its group before it is written, so dk and dv are summed over the
group in the float32 accumulators and leave the kernel as ``[b, hk, sk,
d]``.  At equal counts every call is the program it was (counter
``hvd_flash_kv_group_traced_total`` says which calls were grouped).
v's head size is its own (latent attention scores 192 columns and weighs
128): ``o``, ``do`` and ``dv`` are as wide as v, ``dq`` and ``dk`` as q and
k, and nothing is padded in HBM; where the two sizes are equal the kernels
lower as they did when there was one.

What a query row may see is one static description, a :class:`Mask`, taken
by forward, dq, dkv and :func:`tile_census` alike: none, causal in global
positions (above), a *sliding window* (:func:`sliding_window_mask`: row ``i``
sees keys ``j`` with ``i - window < j <= i``, global positions too; the live
key tiles of a query block are a range that starts where the window does,
crossed at both ends, and the launchers fit the grid and the tiles to it:
the streamed axis has as many steps as a resident block's window reaches
blocks, step ``j`` names the ``j``-th of them from the offsets, and the
tiles a caller gets who names none are half the window long:
:func:`_window_steps`, :func:`default_blocks`), or *block diffusion*
(:func:`block_diffusion_mask`): the
rows are a noised copy of ``noised`` tokens followed by their clean copy,
cut into blocks of ``block``; a clean row sees the clean blocks up to its
own, a noised row the clean blocks before its own and the noised tokens of
its own block.  The kernels are the same ones: the mask says which tiles of
a grid step's block are live and which of those are full (scalar
arithmetic, :func:`_kv_tiles_seen` / :func:`_q_tiles_seen`), which blocks
the grid visits at all (:func:`_kv_blocks_seen` / :func:`_q_blocks_seen`
for the flattened grid's table; on the rectangle the block a skipped step
names so that nothing is fetched, :func:`_nearest_live`;
:func:`_window_block` on a fitted grid), and which pairs of a crossed tile
count (:func:`_seen`).  Tiles are fitted to
the noised half so that none straddles the two copies: of the ``2L x 2L``
scores one quadrant is empty, one block-diagonal and two block-lower-
triangular, ``L^2 + L block`` pairs where a causal mask over ``2L`` rows
allows ``2 L^2``.

On the CPU test mesh, and only there, the kernels run in Pallas
interpreter mode, which keeps every test oracle-checkable on the
8-device virtual slice.
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf, written over masked scores.
NEG_INF = -1e30
# Where a row's running maximum starts.  Above NEG_INF on purpose: a row that
# is masked whole then computes exp(NEG_INF - M_INIT) = 0 and not
# exp(NEG_INF - NEG_INF) = 1, so no second select has to zero ``p``, the row
# sums stay 0 and the statistics handed on (m >= M_INIT) keep the backward
# kernels' exp(s - lse) at 0 there too.
M_INIT = NEG_INF / 2

# One score tile is block_q x block_k; a grid step streams TILES_PER_STEP
# tiles' worth of the other operand (keys for forward and dq, query rows for
# dkv).  From PR 25's sweep on a v5e at [8,12,1024,64] and [1,12,16384,64]
# (the table in the module's docstring): 1024 query rows beat 512 by 6-7%
# in forward and dq at 16k and lose at 1k, where _check_blocks fits them to
# half the sequence; 1024 keys a tile change under 1%; a score block past
# 8 MB (1024 x 4096, 2048 x 2048) runs four to eight times slower.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 512
TILES_PER_STEP = 4


def default_blocks(head_dim: int, mask=None):
    """``(block_q, block_k)`` a caller gets who names none, by q's head
    size: 1024 x 512, swept at head size 64 (PR 25), run, not swept, at
    128 (PR 30), and swept at latent attention's 192 with v at 128 (PR 34,
    one v5e, ``[1, 32, 8192, 192 / 128]`` bf16 causal, us a 512 x 512 tile
    fwd / dq / dkv: 256 x 512 2.32 / 2.91 / 2.78, 512 x 256 2.02 / 2.68 /
    2.86, 512 x 512 2.03 / 2.70 / 2.59, 1024 x 256 1.91 / 2.67 / 2.81,
    **1024 x 512 1.92 / 2.61 / 2.62**, 2048 x 256 1.96 / 2.81 / no room;
    1024 keys a tile pass Mosaic's 16 MiB of VMEM in the forward).  dkv
    streams four query tiles a grid step, and at head size 256 with
    1024-row tiles that is 16 MiB of VMEM, the compiler's whole limit (the
    step compiled or not by where XLA put the kernel's outputs: PR 26):
    512-row tiles over head size 192.

    Under a sliding window (``mask`` a :func:`sliding_window_mask`) the
    rows of a tile are half the window's length (:func:`_window_rows`), a
    grid step streams no more keys than the window is long
    (:func:`_tiles_per_step`), and forward and dq hold up to 1024 rows
    whose 512-row chunks each take the key tiles their own rows reach
    (:func:`_row_tiles`).  Swept on one v5e (PR 39,
    ``chip_smoke.sliding_window_sweep``) at ``[1, 32, 16384, 128]`` bf16
    under a window of 1024, each kernel alone, us an *allowed* 512 x 512
    pair-tile (1984 a call) fwd / dq / dkv, with one tile of rows a
    resident block:

    ============  ==================  ==================  ==================
    rows x keys   one tile a step     two                 four
    ============  ==================  ==================  ==================
    256 x 256     5.59 / 4.51 / 4.87  3.97 / 3.69 / 4.44  3.43 / 3.50 / 4.37
    256 x 512     3.97 / 3.73 / 4.09  3.44 / 3.49 / 3.76  3.80 / 4.47 / 3.66
    512 x 256     5.09 / 3.91 / 3.70  2.98 / 2.98 / 3.57  2.68 / 2.93 / 4.01
    512 x 512     2.97 / 2.96 / 3.15  2.68 / 2.91 / 3.04  3.08 / 3.69 / 3.31
    1024 x 256    6.07 / 4.30 / 4.06  3.40 / 3.40 / 4.26  2.84 / 3.22 / 4.62
    1024 x 512    3.41 / 3.39 / 3.72  2.81 / 3.20 / 3.91  2.84 / 3.53 / 4.09
    ============  ==================  ==================  ==================

    (1024-row tiles there took the tiles the whole block's rows reach: four
    for three; the causal tiles over the grid of every block, as before
    PR 39, 3.34 / 4.28 / 4.48) and at 512 x 512 over the tiles of rows
    forward and dq hold (dkv holds one tile of keys: 3.04-3.29 throughout):

    ==================  ===============  ===============  ===============
    tiles of rows held  one key tile     two a step       four
    ==================  ===============  ===============  ===============
    one                 2.95 / 2.97      2.68 / 2.92      3.07 / 3.71
    two                 2.96 / 3.20      **2.44 / 2.62**  2.87 / 3.55
    four                3.06 / 3.26      2.51 / 2.71      2.82 / 3.18
    ==================  ===============  ===============  ===============

    (256 x 256 with four tiles of rows held 2.76 / 2.76 / 4.33, 256 x 512
    2.77 / 2.76 / 3.77).  At the chip's peak the products of a *visited*
    tile need 0.68 / 1.02 / 1.36 at head size 128; a chunk visits three
    tiles for two allowed."""
    block_q = 512 if head_dim > 192 else DEFAULT_BLOCK_Q
    if mask is not None and mask.kind == _SW:
        block_q = _window_rows(block_q, mask.window)
    return block_q, DEFAULT_BLOCK_K


def _window_rows(block_q: int, window: int) -> int:
    """Rows of a tile under a sliding window: half the window's length in
    powers of two (a tile of ``r`` rows visits ``r + window`` keys for
    ``window`` allowed), no fewer than 256 (smaller tiles cost more a pair
    than their masked pairs save) and no more than the causal tile's."""
    return max(min(block_q, 256), min(block_q, 1 << max(
        window // 2, 1).bit_length() - 1))


# Rows of the resident q tile that forward and dq work on at a time, in a
# loop that is not unrolled: Mosaic unrolls everything else, and a kernel's
# code grows with rows x columns of every body (36 kernels of 1024-row
# bodies, nine of them a kernel, made a 110 MB program of the 16k cell's
# step, 8 MB before, and its warm set-up 78 s for 52).  512 costs 2% of
# forward and dq against no loop, 256 costs 8%, 128 costs 30%.
ROW_CHUNK = 512

_DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b

# The three kernels' names: each ``pallas_call``'s ``name=`` and the
# ``jax.named_scope`` it runs under, so a device trace tells forward, dq and
# dkv apart (docs/profiling.md; benchmarks/layer_metrics/flash_*_ms.py).
FWD_KERNEL = "hvd_flash_fwd"
DQ_KERNEL = "hvd_flash_dq"
DKV_KERNEL = "hvd_flash_dkv"
# What the differentiated forward hands the backward kernels beyond its own
# inputs, by ``checkpoint_name``: the output and the rows' log-sum-exp.
# Outside a checkpoint a name lowers to nothing; inside one whose policy
# saves the names (``models/qwen3_next.recomputed``) the layer is recomputed
# without a second forward kernel call.
FLASH_OUT = "hvd_flash_out"
FLASH_LSE = "hvd_flash_lse"
# What :func:`flash_attention` does round its kernels, under one scope: the
# swaps between the model's ``[b, s, h, d]`` and the kernels' ``[b, h, s,
# d]``, the rows' log-sum-exp from the forward kernel's statistics, and
# the backward pass's ``delta`` (the row sums of ``do * o``).  A scope is
# metadata: the program lowers as without it.
LAYOUT_SCOPE = "hvd_flash_layout"


class Mask(NamedTuple):
    """What a query row may see: static, hashable, one for all three
    kernels and the census.  ``kind`` is ``"none"``, ``"causal"`` (global
    positions, moved by the offsets), ``"sliding_window"`` (causal and no
    further back than ``window``: :func:`sliding_window_mask`) or
    ``"block_diffusion"`` (see :func:`block_diffusion_mask`)."""
    kind: str = "none"
    block: int = 0    # block diffusion: tokens a block
    noised: int = 0   # block diffusion: rows of the noised copy
    window: int = 0   # sliding window: keys a row sees, itself among them

    @property
    def label(self) -> str:
        """The ``mask`` label of ``hvd_flash_tiles_traced_total``."""
        return (self.kind + (f"_b{self.block}" if self.block else "")
                + (f"_w{self.window}" if self.window else ""))


NO_MASK = Mask()
CAUSAL = Mask("causal")
_BD = "block_diffusion"
_SW = "sliding_window"


def sliding_window_mask(window: int) -> Mask:
    """Causal attention no further back than ``window`` keys: query ``i``
    sees key ``j`` iff ``i - window < j <= i`` (itself and the ``window -
    1`` keys before it), in global positions moved by the offsets as the
    causal case's are."""
    window = int(window)
    if window < 1:
        raise ValueError(f"a window of {window} keys sees nothing")
    return Mask(_SW, window=window)


def block_diffusion_mask(block: int, noised: int) -> Mask:
    """The training mask of block diffusion over ``[noised copy ; clean
    copy]``, ``noised`` rows each, in blocks of ``block`` tokens.  With
    ``c`` 0 for a noised and 1 for a clean row and ``g`` the block of a
    row's position, query ``i`` sees key ``j`` iff ``(c_j = 1 and g_j < g_i
    + c_i) or (c_i = 0 and c_j = 0 and g_j = g_i)``."""
    block, noised = int(block), int(noised)
    if block < 1 or noised % block:
        raise ValueError(f"blocks of {block} do not tile {noised} rows")
    return Mask(_BD, block, noised)


def _as_mask(mask) -> Mask:
    """A :class:`Mask` from one, or from the ``causal`` flag the callers
    of old pass."""
    if isinstance(mask, Mask):
        return mask
    return CAUSAL if mask else NO_MASK


def _on_tpu() -> bool:
    """True if the devices the framework runs on are TPUs.

    The mesh devices, not ``jax.devices()[0]``, are authoritative: the test
    harness runs an 8-device *CPU* mesh even when a TPU backend is present
    (conftest.py), and there the kernels must take the interpreter path.
    A backend that fails to come up raises here: turning that into
    interpreter mode would hide a broken chip behind a slow correct run.
    """
    from .. import core

    dev = (core.mesh().devices.flat[0] if core.is_initialized()
           else jax.devices()[0])
    return dev.platform == "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def _static_offsets(q_offset, kv_offset):
    """``(q_offset, kv_offset)`` when both are Python ints, else ``None``:
    what the tile counter can count at trace time."""
    if isinstance(q_offset, (int, np.integer)) and isinstance(
            kv_offset, (int, np.integer)):
        return int(q_offset), int(kv_offset)
    return None


def _offsets(q_offset, kv_offset):
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(())
    kv_offset = jnp.asarray(kv_offset, jnp.int32).reshape(())
    return jnp.stack([q_offset, kv_offset])


def _fit_block(seq: int, cap: int) -> int:
    """Largest divisor of ``seq`` that is <= ``cap``, preferring a
    lane-aligned multiple of 8 (MXU tiling) — but only when alignment
    doesn't collapse the block (e.g. seq 136: plain 68 beats aligned 8)."""
    cap = min(cap, seq)
    aligned = next(
        (b for b in range(cap, 0, -1) if seq % b == 0 and b % 8 == 0), 0
    )
    plain = next((b for b in range(cap, 0, -1) if seq % b == 0), 1)
    return aligned if aligned * 4 >= plain else plain


def _check_blocks(sq, sk, block_q, block_k, mask=False):
    """Fit the tile to the seq lengths: the grid must tile exactly (a
    non-dividing seq would silently truncate the grid and leave the tail
    of the output uninitialized), so shrink each side to the largest
    divisor of its seq length instead of erroring on shapes like 192/128.
    A causal tile takes at most half of either length, so that a short
    sequence still has a tile above the diagonal to skip (at 1024 tokens
    a 1024-row tile computes four 512 x 512 quarters where three do).  A
    block-diffusion tile divides the noised half, so that it lies in one
    copy."""
    mask = _as_mask(mask)
    if mask.kind == _BD:
        if sq != 2 * mask.noised or sk != sq:
            raise ValueError(
                f"a block-diffusion mask over {mask.noised} noised rows "
                f"takes {2 * mask.noised} queries and keys, not {sq} and "
                f"{sk}")
        sq = sk = mask.noised
    elif mask.kind in ("causal", _SW):
        block_q = min(block_q, max(sq // 2, 1))
        block_k = min(block_k, max(sk // 2, 1))
    return _fit_block(sq, block_q), _fit_block(sk, block_k)


def _tiles_per_step(seq: int, tile: int, mask: Mask = NO_MASK) -> int:
    """How many ``tile``-wide tiles of the streamed operand one grid step
    takes: the most up to TILES_PER_STEP that tile ``seq`` exactly (under
    block diffusion the noised half, so that a step's block lies in one
    copy)."""
    n = (mask.noised if mask.kind == _BD else seq) // tile
    most = TILES_PER_STEP
    if mask.kind == _SW:
        # a step's block no longer than the window: a block of four tiles
        # under a window of two is fetched for the two its rows reach
        most = max(1, min(most, mask.window // tile))
    return next(c for c in range(min(most, n), 0, -1) if n % c == 0)


def _kv_grid(sq, sk, block_q, block_k, mask, static_offs=None):
    """Forward's and dq's ``(block_q, tile_k, block_k, steps, chunk)``: the
    tile fitted to the lengths, the keys one grid step streams, how many
    steps a query block takes over them on the rectangle (every block of
    keys there is, or under a sliding window the few its rows can reach:
    :func:`_window_steps`; with offsets that are Python ints, the most any
    query block does reach; a call on the flattened grid takes its steps
    from :func:`_pair_table` instead) and the rows the kernels work on at a
    time.  Under a sliding window the resident block is several tiles of
    rows (:func:`_row_tiles`), each of which takes the key tiles its own
    rows reach."""
    block_q, tile_k = _check_blocks(sq, sk, block_q, block_k, mask)
    chunk = _fit_block(block_q, ROW_CHUNK)
    block_k = tile_k * _tiles_per_step(sk, tile_k, mask)
    steps = sk // block_k
    if mask.kind == _SW:
        block_q *= _row_tiles(sq, block_q)
        steps = _window_steps(mask, block_q, block_k, steps)
        if static_offs is not None:
            steps = max(1, *map(len, _kv_blocks_seen(
                mask, sq, sk, block_q, block_k, static_offs)))
    return block_q, tile_k, block_k, steps, chunk


def _row_tiles(sq: int, tile_q: int) -> int:
    """Tiles of rows in forward's and dq's resident block under a sliding
    window: up to DEFAULT_BLOCK_Q rows, so that a block of keys is fetched
    once for them all; at most half the rows, as a causal tile."""
    n = sq // tile_q
    most = max(1, min(DEFAULT_BLOCK_Q // tile_q, n // 2))
    return next(c for c in range(most, 0, -1) if n % c == 0)


def _q_grid(sq, sk, block_q, block_k, mask, static_offs=None):
    """dkv's ``(tile_q, block_q, block_k, steps)``, the roles swapped: the
    query rows one grid step streams and the steps a block of keys takes
    over them."""
    tile_q, block_k = _check_blocks(sq, sk, block_q, block_k, mask)
    block_q = tile_q * _tiles_per_step(sq, tile_q, mask)
    steps = sq // block_q
    if mask.kind == _SW:
        steps = _window_steps(mask, block_k, block_q, steps)
        if static_offs is not None:
            steps = max(1, *map(len, _q_blocks_seen(
                mask, sq, sk, block_q, block_k, static_offs)))
    return tile_q, block_q, block_k, steps


def _scale_parts(scale: float, dtype):
    """``(operand factor, score factor)``, one of them ``None``.  The logit
    scale is multiplied into the ``[block, d]`` operand when that is exact
    in its dtype (a power of two, as 1/8 for head_dim 64), and stays on the
    float32 scores otherwise."""
    exact = (math.frexp(scale)[0] == 0.5
             and jnp.issubdtype(dtype, jnp.floating))
    return (scale, None) if exact else (None, scale)


def _clip(x, lo, hi):
    if isinstance(x, int):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _where(cond, a, b):
    if isinstance(cond, (bool, np.bool_)):
        return a if cond else b
    return jnp.where(cond, a, b)


def _most(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _kv_tiles_seen(mask, q_first, q_rows, k_first, n, width):
    """Of ``n`` key tiles of ``width`` that start at key ``k_first``:
    ``(first, full, live)``: the query rows ``[q_first, q_first + q_rows)``
    see any of tiles ``[first, first + live)`` and no other, and ``full``
    of them whole (nothing to mask).  Python ints or traced scalars.

    Causal (positions): the full tiles come first (last key <= first row),
    the crossed ones after them and the skipped ones last; ``first`` is 0.
    Sliding window (positions): the live tiles start where the first row's
    window does and end at the last row's diagonal; the full ones (no later
    than the first row, inside the last row's window) lie between crossed
    ones at both ends.
    Block diffusion (row indices; the rows lie in one copy and so do the
    tiles): clean keys are seen like that up to a row's horizon, the end of
    its own block from a clean row and of the block before from a noised
    one; noised keys are seen by the noised rows of their block alone, so
    the live tiles are the few that hold the rows' own blocks."""
    if mask.kind == "causal":
        full = _clip(q_first - k_first + 1, 0, n * width) // width
        live = _clip(q_first + q_rows - 1 - k_first + width, 0,
                     n * width) // width
        return 0, full, live
    if mask.kind == _SW:
        span, back = n * width, q_first - k_first - mask.window + 1
        first = _clip(back, 0, span) // width
        end = _clip(q_first + q_rows - 1 - k_first + width, 0,
                    span) // width
        full = (_clip(q_first - k_first + 1, 0, span) // width
                - _clip(back + q_rows - 1 + width - 1, 0, span) // width)
        return first, _most(full, 0), _most(end - first, 0)
    if mask.kind != _BD:
        return 0, n, n
    size, half, span = mask.block, mask.noised, n * width
    q_clean, k_clean = q_first >= half, k_first >= half
    c = _where(q_clean, 1, 0)
    qp, kp = q_first - c * half, k_first - _where(k_clean, half, 0)
    g_lo, g_hi = qp // size, (qp + q_rows - 1) // size
    # clean keys: every row's horizon lies between the first row's and the
    # last row's
    full_c = _clip((g_lo + c) * size - kp, 0, span) // width
    live_c = _clip((g_hi + c) * size - kp + width - 1, 0, span) // width
    # noised keys, noised rows: positions [lo, hi) of the rows' own blocks
    lo, hi = g_lo * size - kp, (g_hi + 1) * size - kp
    first_d = _clip(lo, 0, span) // width
    live_d = _most(_clip(hi + width - 1, 0, span) // width - first_d, 0)
    # whole only where the rows share one block and the tile lies inside it
    full_d = _where(g_lo == g_hi, _most(
        _clip(hi, 0, span) // width
        - _clip(lo + width - 1, 0, span) // width, 0), 0)
    return (_where(k_clean, 0, first_d),
            _where(k_clean, full_c, _where(q_clean, 0, full_d)),
            _where(k_clean, live_c, _where(q_clean, 0, live_d)))


def _q_tiles_seen(mask, k_first, k_rows, q_first, n, width):
    """dkv's side of the same: of ``n`` query tiles of ``width`` rows that
    start at row ``q_first``, the half-open range ``(lo, hi)`` of those
    that see any of the keys ``[k_first, k_first + k_rows)``."""
    if mask.kind == "causal":
        # query tiles wholly before the first key come first
        return _clip(k_first - q_first, 0, n * width) // width, n
    if mask.kind == _SW:
        # and the last one starts no later than the last key's window ends
        lo = _clip(k_first - q_first, 0, n * width) // width
        hi = _clip(k_first + k_rows + mask.window - 2 - q_first + width, 0,
                   n * width) // width
        return lo, _most(hi, lo)
    if mask.kind != _BD:
        return 0, n
    size, half, span = mask.block, mask.noised, n * width
    q_clean, k_clean = q_first >= half, k_first >= half
    c = _where(q_clean, 1, 0)
    qp, kp = q_first - c * half, k_first - _where(k_clean, half, 0)
    # clean keys: the rows whose horizon passes the first key
    lo_c = _clip((kp // size + 1 - c) * size - qp, 0, span) // width
    # noised keys: the noised rows of the keys' own blocks
    lo_d = _clip(kp // size * size - qp, 0, span) // width
    hi_d = _clip(((kp + k_rows - 1) // size + 1) * size - qp + width - 1,
                 0, span) // width
    return (_where(k_clean, lo_c, _where(q_clean, n, lo_d)),
            _where(k_clean, n, _where(q_clean, n, _most(hi_d, lo_d))))


def _nearest_live(i, ranges):
    """The block a grid step names on the streamed side: block ``i`` where
    it is live, else the next live one, else the last, so that a skipped
    step changes no index and fetches nothing.  ``ranges``: the live blocks
    ``[a0, a1)`` of the first copy and ``[b0, b1)`` of the second."""
    (a0, a1), (b0, b1) = ranges
    return jnp.where(
        jnp.logical_and(a0 < a1, i < a1), jnp.maximum(i, a0),
        jnp.where(b0 < b1, jnp.clip(i, b0, b1 - 1), jnp.maximum(a1 - 1, 0)))


def _kv_blocks_seen(mask, sq, sk, rows, keys, offs):
    """The blocks of ``keys`` keys that each block of ``rows`` query rows
    sees any of, in their order, at the Python-int offsets ``offs``: the
    live steps of forward's and dq's grid, a list a query block (both
    copies' under block diffusion)."""
    copies = 2 if mask.kind == _BD else 1
    n = sk // keys // copies
    seen = []
    for i in range(sq // rows):
        seen.append([])
        for part in range(copies):
            first, _, live = _kv_tiles_seen(
                mask, offs[0] + i * rows, rows, offs[1] + part * mask.noised,
                n, keys)
            seen[-1] += range(part * n + first, part * n + first + live)
    return seen


def _q_blocks_seen(mask, sq, sk, rows, keys, offs):
    """dkv's side of the same: the blocks of ``rows`` query rows that see
    any of each block of ``keys`` keys (under block diffusion two ranges:
    the noised rows of the keys' own blocks and the clean rows after
    them)."""
    copies = 2 if mask.kind == _BD else 1
    n = sq // rows // copies
    seen = []
    for j in range(sk // keys):
        seen.append([])
        for part in range(copies):
            lo, hi = _q_tiles_seen(
                mask, offs[1] + j * keys, keys, offs[0] + part * mask.noised,
                n, rows)
            seen[-1] += range(part * n + lo, part * n + hi)
    return seen


# The most (resident block, streamed block) pairs a flattened grid's table
# holds: three int32 words a pair of the scalar memory the table is
# prefetched into.  A v5e has 1 MiB of it: compiled for a described one, a
# causal call of 33 024 pairs (65 536 rows in 128 x 512 blocks, 387 KiB)
# passes and one of 131 584 is refused (PR 43).  The cells' calls have
# 48-80 pairs a head; 131 072 rows at the default tiles have 16 512.
MAX_PAIRS = 32768


def _pair_table(seen, steps, mask, sq, sk, rows, keys, static_offs,
                group=1):
    """The table of a flattened grid, or ``None`` where the launchers keep
    the rectangle of ``steps`` steps a resident block.  Where the mask is
    causal or block diffusion and the offsets are Python ints, the live
    (resident block, streamed block) pairs are known when the call is
    traced, and the streamed axis of the grid is the list of them, in the
    resident block's order: ``seen`` gives that list, a resident block at a
    time (:func:`_kv_blocks_seen` for forward and dq, a block of ``rows``
    query rows resident; :func:`_q_blocks_seen` for dkv, a block of
    ``keys`` keys).  The table
    is ``[resident block of every step | streamed block of every step |
    edge of every step]``, int32 rows of one array, ``edge`` 1 at a
    resident block's first
    pair (the accumulators are zeroed) plus 2 at its last (the outputs are
    written); a resident block's pairs are consecutive, so its blocks are
    fetched and its outputs written back once.  A resident block that sees
    nothing keeps one step, whose body finds no tile to compute, for its
    outputs' zeros.  dkv under a ``group`` of q heads a kv head takes a
    resident block's pairs once a member of the group, one member after
    the other, between one zeroing and one write, and a fourth row says
    which member a step is (the index maps of q's side read it; at a group
    of 1 there is none, and the table is the one it was).  A sliding window
    keeps its fitted grid (its steps are
    a range a block, found by arithmetic: :func:`_window_steps`), traced
    offsets the rectangle (where the live blocks start is data), and so do
    a call of more than ``MAX_PAIRS`` pairs and one whose rectangle has no
    idle step (the short calls, a block or two of keys: the table would
    drop nothing, and they keep the programs they had)."""
    if static_offs is None or mask.kind not in ("causal", _BD):
        return None
    blocks = seen(mask, sq, sk, rows, keys, static_offs)
    resident, streamed, edge, member = [], [], [], []
    for block, live in enumerate(blocks):
        live = live or [0]
        member += [m for m in range(group) for _ in live]
        live = live * group
        resident += [block] * len(live)
        streamed += live
        edge += [(n == 0) + 2 * (n == len(live) - 1)
                 for n in range(len(live))]
    table = [resident, streamed, edge] + [member] * (group > 1)
    if (len(resident) * len(table) > 3 * MAX_PAIRS
            or len(resident) == len(blocks) * steps * group):
        return None
    return np.array(table, np.int32)


def _window_steps(mask, resident, block, n):
    """Steps of the streamed axis of the grid under a sliding window: the
    blocks of ``block`` that ``resident`` rows (dkv: keys) can reach, the
    ``resident + window - 1`` positions of their windows wherever those
    start in a block, and never more than the ``n`` there are (a window as
    long as the sequence: the causal grid)."""
    return min(n, -(-(resident + mask.window - 1) // block) + 1)


def _window_block(step, first, count, n):
    """``(block, live)`` of step ``step`` of a fitted range: the live
    blocks are ``[first, first + count)`` of ``n`` and step ``j`` names
    block ``first + j``; a step past the last live one names that one again
    (nothing is fetched) and is not ``live`` (nothing is computed).  The
    index map and the kernel's body both ask here, so a body masks against
    the positions of the block it was handed and no other."""
    return (_clip(first + jnp.minimum(step, count - 1), 0, n - 1),
            step < count)


def _window_kv_block(mask, q_first, q_rows, k_origin, n, width, step):
    """Forward's and dq's: the block of ``width`` keys, of the ``n`` from
    key ``k_origin``, that step ``step`` of the query rows ``[q_first,
    q_first + q_rows)`` names."""
    first, _, count = _kv_tiles_seen(mask, q_first, q_rows, k_origin, n,
                                     width)
    return _window_block(step, first, count, n)


def _window_q_block(mask, k_first, k_rows, q_origin, n, width, step):
    """dkv's: the block of ``width`` query rows, of the ``n`` from row
    ``q_origin``, that step ``step`` of the keys ``[k_first, k_first +
    k_rows)`` names."""
    lo, hi = _q_tiles_seen(mask, k_first, k_rows, q_origin, n, width)
    return _window_block(step, lo, hi - lo, n)


def _seen(mask, shape, q_first, k_first, r, rows, col=0, keys_down=False):
    """Which pairs of a score tile the mask allows, boolean ``shape``: the
    tile's first query is ``q_first + r * rows``, its first key ``k_first +
    col``; ``keys_down`` for dkv's transposed scores (keys down the rows,
    queries along the lanes)."""
    if mask.kind == "causal":
        if keys_down:
            return _row_minus_col(*shape) <= q_first + r * rows - k_first
        return _row_minus_col(*shape) >= k_first - q_first - r * rows
    if mask.kind == _SW:
        # query minus key, in [0, window)
        ahead = q_first + r * rows - k_first - col
        diff = _row_minus_col(*shape)
        if keys_down:
            return jnp.logical_and(diff <= ahead, diff > ahead - mask.window)
        return jnp.logical_and(diff >= -ahead, diff < mask.window - ahead)
    size, half, row = mask.block, mask.noised, r * rows
    q_axis = 1 if keys_down else 0
    q_shape = (1, shape[1]) if keys_down else (shape[0], 1)
    k_shape = (shape[0], 1) if keys_down else (1, shape[1])
    qi = q_first + row + lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    kj = k_first + col + lax.broadcasted_iota(jnp.int32, k_shape,
                                              1 - q_axis)
    # the tile lies in one copy of the queries and one of the keys
    q_clean, k_clean = q_first >= half, k_first >= half
    c = jnp.where(q_clean, 1, 0)
    start = (qi - c * half) // size * size      # of the row's own block
    # keys [lo, hi) in the tile's copy: clean ones up to the row's horizon,
    # noised ones of the row's own block (none for a clean row)
    lo = jnp.where(k_clean, half, start)
    hi = jnp.where(k_clean, half + start + c * size,
                   jnp.where(q_clean, 0, start + size))
    return jnp.logical_and(kj >= lo, kj < hi)


def tile_census(sq, sk, block_q, block_k, causal, q_offset=0, kv_offset=0):
    """How many ``block_q x block_k`` score tiles of one head are
    ``skipped`` (no row sees a key: not computed), ``full`` (every row sees
    every key: nothing to mask) and ``crossed`` (the mask's edge passes
    through).  ``causal`` is a :class:`Mask` or the flag.  The blocks are
    fitted to the lengths as the kernels fit them."""
    mask = _as_mask(causal)
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k, mask)
    nq, nk = sq // block_q, sk // block_k
    # a copy's keys at a time: one range of live tiles in each
    copies = 2 if mask.kind == _BD else 1
    counts = {"skipped": 0, "full": 0, "crossed": 0}
    for i in range(nq):
        for part in range(copies):
            _, full, live = _kv_tiles_seen(
                mask, q_offset + i * block_q, block_q,
                kv_offset + part * mask.noised, nk // copies, block_k)
            counts["full"] += full
            counts["crossed"] += live - full
            counts["skipped"] += nk // copies - live
    return counts


def grid_census(sq, sk, block_q, block_k, causal, q_offset=0, kv_offset=0,
                group=1):
    """The grid steps a q head's three kernels take on their streamed axis,
    ``{"fwd": {"launched": ..., "live": ...}, "dq": ..., "dkv": ...}``: a
    ``live`` step's block holds a tile somebody sees, every other step is
    visited to compute nothing and fetch nothing (``live`` is ``None`` where
    an offset is not a Python int: the launched extent is static, where the
    live blocks start is data).  ``causal`` is a :class:`Mask` or the flag;
    blocks and tiles a step are fitted as the launchers fit them, and the
    launched extent is that of the grid they run: the pairs of a flattened
    grid's table (:func:`_pair_table`) where there is one.  ``group``: the q
    heads a kv head; dkv's steps go by q heads as forward's and dq's do (a
    kv head's grid takes its group's in turn), so the counts are those of
    the call at equal head counts wherever the group's table fits."""
    mask = _as_mask(causal)
    offs = _static_offsets(q_offset, kv_offset)

    def side(seen, resident, rows, keys, steps, group=1):
        table = _pair_table(seen, steps, mask, sq, sk, rows, keys, offs,
                            group)
        return {"launched": (resident * steps if table is None
                             else table.shape[1] // group),
                "live": offs and sum(map(len, seen(mask, sq, sk, rows, keys,
                                                   offs)))}

    rows, _, keys, steps, _ = _kv_grid(sq, sk, block_q, block_k, mask, offs)
    kv_side = side(_kv_blocks_seen, sq // rows, rows, keys, steps)
    _, rows, keys, steps = _q_grid(sq, sk, block_q, block_k, mask, offs)
    q_side = side(_q_blocks_seen, sk // keys, rows, keys, steps, group)
    return {"fwd": kv_side, "dq": dict(kv_side), "dkv": q_side}


def _kv_group(q, k, v) -> int:
    """The q heads a kv head serves, from the shapes (``[b, heads, s, d]``):
    q head ``i`` reads kv head ``i // group``."""
    h, hk = q.shape[1], k.shape[1]
    if v.shape[1] != hk or h % hk:
        raise ValueError(
            f"{h} q heads do not share {hk} k heads and {v.shape[1]} v "
            f"heads evenly")
    return h // hk


def _count_tiles(kernel, static_offs, q, k, v, block_q, block_k, mask):
    """The trace-time counters: once for every kernel call that is traced."""
    from .. import metrics

    (b, h, sq, _), sk = q.shape, k.shape[2]
    group = _kv_group(q, k, v)
    metrics.record_flash_kv_group(kernel, h, h // group)
    steps = grid_census(sq, sk, block_q, block_k, mask,
                        *(static_offs or (None, None)), group=group)[kernel]
    if steps["live"] is not None:
        steps["idle"] = steps["launched"] - steps["live"]
    metrics.record_flash_grid_steps(
        kernel, {kind: n * b * h for kind, n in steps.items()
                 if n is not None}, mask.label)
    if static_offs is None and mask.kind in ("causal", _SW):
        block_q, block_k = _check_blocks(sq, sk, block_q, block_k, mask)
        counts = {"dynamic": (sq // block_q) * (sk // block_k)}
    else:
        counts = tile_census(sq, sk, block_q, block_k, mask,
                             *(static_offs or (0, 0)))
    metrics.record_flash_tiles(
        kernel, {kind: n * b * h for kind, n in counts.items()}, mask.label)


def _count_residuals(kernel, *kept):
    """The trace-time counter of a differentiated forward: the bytes it
    hands its backward beyond its own inputs (ops/gated_delta.py shares
    it)."""
    from .. import metrics

    metrics.record_kernel_residual_bytes(
        kernel, sum(x.size * x.dtype.itemsize for x in kept))


def _row_minus_col(rows, cols):
    return (lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _chunk(i, size):
    """Rows ``[i * size, (i + 1) * size)`` of a block, ``i`` a loop's index."""
    return pl.ds(pl.multiple_of(i * size, size), size)


def _loop(lo, hi, body):
    """``body(i)`` for ``i`` in ``[lo, hi)``, not unrolled."""
    lax.fori_loop(lo, hi, lambda i, carry: (body(i), carry)[1], None)


def _sum_lanes(width):
    """Lanes of the forward kernel's running row sums: partial sums a lane
    (one add a vreg, no reduction across lanes until the q block is done)
    where the tile's width allows, else the sum itself."""
    return 128 if width % 128 == 0 else 1


def _fold_lanes(p, lanes):
    if lanes == 1:
        return jnp.sum(p, axis=-1, keepdims=True)
    return sum(p[:, c:c + lanes] for c in range(0, p.shape[1], lanes))


def _tiles(start, width, tile):
    """The rows of tiles ``[start, start + width)`` of a block; ``start``
    is 0 or a traced scalar."""
    if isinstance(start, int):
        return pl.ds(start * tile, width * tile)
    return pl.ds(pl.multiple_of(start * tile, tile), width * tile)


def _each_chunk_its_tiles(block, mask, live_step, q_first, bq, chunk,
                          k_first, n_tiles, tile_k, unmasked):
    """What a grid step of forward or dq does with its kv block under a
    sliding window: every chunk of the resident rows takes the tiles its
    own rows see (:func:`_each_kind_of_block` a chunk: a static width, a
    traced start), so a resident block of two chunks multiplies the pairs
    of one chunk's window twice and not those of both windows' span; no
    tile where the step is past the last live block (it was handed that
    block again)."""
    def rows(r):
        first, full, live = _kv_tiles_seen(
            mask, q_first + r * chunk, chunk, k_first, n_tiles, tile_k)
        _each_kind_of_block(
            functools.partial(block, rows=r), n_tiles, first,
            jnp.where(live_step, full, 0), jnp.where(live_step, live, 0),
            unmasked)

    _loop(0, bq // chunk, rows)


def _each_kind_of_block(block, n_tiles, first, full, live, unmasked):
    """What a grid step of forward or dq does with its kv block of
    ``n_tiles`` tiles, of which its query rows see tiles ``[first, first +
    live)`` at all and ``full`` whole.  Nothing where they see none.  Where
    the mask's edge crosses the block, ``block(width, True, start)`` over
    the tiles they see any of, rounded up to half a block (``start`` moved
    back where that would pass the block's end; a tile nobody sees is
    masked whole): a body of static width each, so the compiler schedules
    it whole, and two of them, because every body is code (Mosaic unrolls
    it) that every layer's kernel carries.  With ``unmasked``,
    ``block(n_tiles, False, 0)`` where they see all of it whole; without,
    those blocks take the masked body too."""
    half = max(1, n_tiles // 2)
    crossed = live > 0
    if unmasked:
        pl.when(full == n_tiles)(lambda: block(n_tiles, False, 0))
        crossed = jnp.logical_and(crossed, full < n_tiles)
    for width in range(half, n_tiles + 1, half):
        pl.when(jnp.logical_and(crossed, (live + half - 1) // half * half
                                == width))(
            functools.partial(block, width, True,
                              _clip(first, 0, n_tiles - width)))


def _grid_step(offs_ref, pairs, group=1):
    """Which blocks a grid step of a kernel's body works on: ``(streamed
    block, resident block, first, last)``, the block of the streamed side
    and three functions, each of which emits its scalar arithmetic where
    the body asks: the resident block, and whether the step is the
    resident block's first (zero the accumulators) and its last (write the
    outputs).  On the rectangle the grid's two inner indices say; on a
    flattened grid of ``pairs`` steps the table behind the two offsets
    does (:func:`_pair_table`).  dkv under a ``group`` of q heads a kv head
    streams the group's members in turn: the rectangle's inner axis is
    ``group`` times as long and a member's step is what is left of the
    index (the table has the members' passes written out)."""
    if pairs:
        t = pl.program_id(2)
        return (offs_ref[2 + pairs + t], lambda: offs_ref[2 + t],
                lambda: offs_ref[2 + 2 * pairs + t] & 1 == 1,
                lambda: offs_ref[2 + 2 * pairs + t] >= 2)
    j = pl.program_id(3)
    return (j if group == 1 else lax.rem(j, pl.num_programs(3) // group),
            lambda: pl.program_id(2), lambda: j == 0,
            lambda: j == pl.num_programs(3) - 1)


def _grid(offs, static_offs, table, rectangle, streamed):
    """What a launcher runs its kernel over: ``(scalar operand, grid, pairs,
    resident, streamed)``, the last two functions of the grid's indices and
    the scalar operand that give a step's block on either side.  Without a
    table the ``rectangle`` ``(b, h, resident blocks, steps)`` under the
    offsets, step ``j`` naming what ``streamed`` says, and ``pairs`` 0.
    With one (:func:`_pair_table`) a grid of a step a pair, the table
    behind the offsets, and both sides read from it: the same resident
    block through a block's pairs, so nothing of it is fetched or written
    back before its last."""
    if table is None:
        return (offs, rectangle, 0, lambda b_, h_, i, j, offs: i, streamed)
    pairs = table.shape[1]
    return (jnp.asarray(np.concatenate([np.array(static_offs, np.int32),
                                        table.ravel()])),
            (*rectangle[:2], pairs), pairs,
            lambda b_, h_, t, offs: offs[2 + t],
            lambda b_, h_, t, offs: offs[2 + pairs + t])


def _at(block, *tail, head=None):
    """Index map of the ``[1, 1, rows, ...]`` blocks of a ``[b, h, s, ...]``
    operand whose block of rows a grid step finds by ``block``, at the
    grid's own head or at the one ``head`` finds (the other side's, where q
    has a group of heads a kv head)."""
    if head is None:
        return lambda *g: (*g[:2], block(*g), *tail)
    return lambda *g: (g[0], head(*g), block(*g), *tail)


def _kv_head(group):
    """``head`` of :func:`_at` for k's and v's blocks on forward's and dq's
    grids, which run over q's heads: q head ``i`` reads kv head ``i //
    group`` (the grid's own at a group of 1)."""
    return None if group == 1 else lambda b_, h_, *_: lax.div(h_, group)


def _jit_kernel(fn):
    """The three launchers are jitted with everything but the arrays
    static, so that a model's layers share one trace of the kernel and one
    lowering of it to Mosaic (the lowering runs in Python on every start,
    cached program or not: 36 separate calls of these kernels put 24 s
    onto the 16k cell's set-up)."""
    return jax.jit(fn, static_argnames=tuple(
        inspect.getfullargspec(fn).kwonlyargs))


def _launch(name, kernel, offs, grid, ins, outs, scratch, interpret):
    """One of the three ``pallas_call``s, under its name.  ``ins`` are
    (array, block shape, index map), ``outs`` (shape, dtype, block shape,
    index map), ``scratch`` float32 VMEM shapes.  The blocks as they are
    fit Mosaic's 16 MiB of scoped VMEM up to head_dim 256 in float32."""
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=_DIM_SEMANTICS[-len(grid):])
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(block, index) for _, block, index in ins],
            out_specs=[pl.BlockSpec(block, index)
                       for _, _, block, index in outs],
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch],
        ),
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                   for shape, dtype, _, _ in outs],
        compiler_params=params,
        interpret=interpret,
        name=name,
    )
    with jax.named_scope(name):
        return call(offs, *(x for x, _, _ in ins))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                acc_ref, mi_ref, li_ref, *,
                mask, scale, normalize, tile_k, blocks, chunk, pairs):
    bq = q_ref.shape[2]
    n_tiles = k_ref.shape[2] // tile_k
    j, resident, first_step, last_step = _grid_step(offs_ref, pairs)
    q_first = offs_ref[0] + resident() * bq
    if mask.kind == _SW:
        named, live_step = _window_kv_block(
            mask, q_first, bq, offs_ref[1], blocks, k_ref.shape[2], j)
        k_first = offs_ref[1] + named * k_ref.shape[2]
    else:
        k_first = offs_ref[1] + j * k_ref.shape[2]
    q_scale, s_scale = _scale_parts(scale, q_ref.dtype)

    @pl.when(first_step())
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        mi_ref[:] = jnp.full_like(mi_ref, M_INIT)
        li_ref[:] = jnp.zeros_like(li_ref)

    def block(width, masked, start, rows=None):
        """One step of the online softmax over ``width`` tiles of the kv
        block from tile ``start``, taken as one: the statistics move
        once.  For every chunk of the resident rows, or for chunk ``rows``
        alone."""
        cols = _tiles(start, width, tile_k)

        def some_rows(r):
            rows = _chunk(r, chunk)
            q = q_ref[0, 0, rows, :]
            if q_scale is not None:
                q = q * q_scale
            s = lax.dot_general(q, k_ref[0, 0, cols, :], _NT,
                                preferred_element_type=jnp.float32)
            if s_scale is not None:
                s = s * s_scale
            if masked:
                s = jnp.where(_seen(mask, s.shape, q_first, k_first, r,
                                    chunk, start * tile_k), s, NEG_INF)
            m_prev = mi_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            mi_ref[rows, :] = m_new
            li_ref[rows, :] = (li_ref[rows, :] * alpha
                               + _fold_lanes(p, li_ref.shape[1]))
            vb = v_ref[0, 0, cols, :]
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + lax.dot_general(
                p.astype(vb.dtype), vb, _NN,
                preferred_element_type=jnp.float32)

        if rows is None:
            _loop(0, bq // chunk, some_rows)
        else:
            some_rows(rows)

    if mask.kind == "none":
        block(n_tiles, False, 0)
    elif mask.kind == _SW:
        _each_chunk_its_tiles(block, mask, live_step, q_first, bq, chunk,
                              k_first, n_tiles, tile_k, unmasked=True)
    else:
        # the mask is a tenth of this kernel's time where it is not needed
        _each_kind_of_block(
            block, n_tiles,
            *_kv_tiles_seen(mask, q_first, bq, k_first, n_tiles, tile_k),
            unmasked=True)

    @pl.when(last_step())
    def _():
        acc = acc_ref[:]
        l = jnp.sum(li_ref[:], axis=-1, keepdims=True)
        if normalize:
            acc = acc / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = acc.astype(o_ref.dtype)
        m_ref[0, 0] = mi_ref[:]
        l_ref[0, 0] = l


def _kv_block(mask, block_q, block_k, sk):
    """The streamed k / v block a step of forward's and dq's rectangle
    names.  A grid
    step wholly past the diagonal computes nothing, so it names the last
    block its query rows do see: the index does not change and nothing is
    fetched.  Under a sliding window the grid has a step for each block the
    rows' windows reach and step ``j`` names the ``j``-th of them, under
    block diffusion the live blocks are a range in each copy of the keys."""
    def index(b_, h_, i, j, offs):
        if mask.kind == "causal":
            last = _clip(offs[0] + i * block_q + block_q - 1 - offs[1],
                         0, sk - 1) // block_k
            j = jnp.minimum(j, last)
        elif mask.kind == _SW:
            j, _ = _window_kv_block(mask, offs[0] + i * block_q, block_q,
                                    offs[1], sk // block_k, block_k, j)
        elif mask.kind == _BD:
            n = mask.noised // block_k
            live = []
            for part in range(2):
                first, _, count = _kv_tiles_seen(
                    mask, i * block_q, block_q, part * mask.noised, n,
                    block_k)
                live.append((part * n + first, part * n + first + count))
            j = _nearest_live(j, live)
        return j

    return index


def _mha_fwd(q, k, v, offs, *, mask, block_q, block_k, interpret,
             static_offs=None, **kw):
    """q ``[b,h,sq,d]``, k ``[b,hk,sk,d]``, v ``[b,hk,sk,dv]``, ``h`` a
    multiple of ``hk`` (q head ``i`` reads kv head ``i // (h // hk)``, by
    the kv blocks' index map); returns ``(o [b,h,sq,dv], m, l)`` with m/l
    ``[b,h,sq,1]``."""
    _count_tiles("fwd", static_offs, q, k, v, block_q, block_k, mask)
    return _fwd_call(q, k, v, offs, mask=mask, block_q=block_q,
                     block_k=block_k, static_offs=static_offs,
                     interpret=_resolve_interpret(interpret), **kw)


@_jit_kernel
def _fwd_call(q, k, v, offs, *, mask, scale, block_q, block_k, normalize,
              interpret, static_offs=None):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_q, tile_k, block_k, steps, chunk = _kv_grid(
        sq, sk, block_q, block_k, mask, static_offs)
    offs, grid, pairs, resident, streamed = _grid(
        offs, static_offs,
        _pair_table(_kv_blocks_seen, steps, mask, sq, sk, block_q, block_k,
                    static_offs),
        (b, h, sq // block_q, steps), _kv_block(mask, block_q, block_k, sk))
    q_index = _at(resident, 0)
    kv_index = _at(streamed, 0, head=_kv_head(_kv_group(q, k, v)))
    kernel = functools.partial(
        _fwd_kernel, mask=mask, scale=scale, normalize=normalize,
        tile_k=tile_k, blocks=sk // block_k, chunk=chunk, pairs=pairs,
    )
    out_dtype = q.dtype if normalize else jnp.float32
    q_block, k_block, row = ((1, 1, block_q, d), (1, 1, block_k, d),
                             (1, 1, block_q, 1))
    # o, like v, is dv wide (the scores come from d columns and weigh dv)
    o_block, v_block = (1, 1, block_q, dv), (1, 1, block_k, dv)
    return _launch(
        FWD_KERNEL, kernel, offs, grid,
        ins=[(q, q_block, q_index), (k, k_block, kv_index),
             (v, v_block, kv_index)],
        outs=[((b, h, sq, dv), out_dtype, o_block, q_index),
              ((b, h, sq, 1), jnp.float32, row, q_index),
              ((b, h, sq, 1), jnp.float32, row, q_index)],
        scratch=[(block_q, dv), (block_q, 1), (block_q, _sum_lanes(tile_k))],
        interpret=interpret)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref, *, mask, scale, tile_k, blocks,
                   chunk, pairs):
    bq = q_ref.shape[2]
    n_tiles = k_ref.shape[2] // tile_k
    j, resident, first_step, last_step = _grid_step(offs_ref, pairs)
    q_first = offs_ref[0] + resident() * bq
    if mask.kind == _SW:
        named, live_step = _window_kv_block(
            mask, q_first, bq, offs_ref[1], blocks, k_ref.shape[2], j)
        k_first = offs_ref[1] + named * k_ref.shape[2]
    else:
        k_first = offs_ref[1] + j * k_ref.shape[2]
    q_scale, s_scale = _scale_parts(scale, q_ref.dtype)

    @pl.when(first_step())
    def _():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def block(width, masked, start, rows=None):
        """dq's share of ``width`` tiles of the kv block from tile
        ``start``, for every chunk of the resident rows or for chunk
        ``rows`` alone."""
        cols = _tiles(start, width, tile_k)

        def some_rows(r):
            rows = _chunk(r, chunk)
            kb = k_ref[0, 0, cols, :]
            q = q_ref[0, 0, rows, :]
            if q_scale is not None:
                q = q * q_scale
            s = lax.dot_general(q, kb, _NT,
                                preferred_element_type=jnp.float32)
            if s_scale is not None:
                s = s * s_scale
            p = jnp.exp(s - lse_ref[0, 0, rows, :])
            if masked:
                # after the exp: a select drops what overflowed where masked
                p = jnp.where(_seen(mask, s.shape, q_first, k_first, r,
                                    chunk, start * tile_k), p, 0.0)
            dp = lax.dot_general(do_ref[0, 0, rows, :], v_ref[0, 0, cols, :],
                                 _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0, rows, :])
            dq_acc_ref[rows, :] += lax.dot_general(
                ds.astype(kb.dtype), kb, _NN,
                preferred_element_type=jnp.float32)

        if rows is None:
            _loop(0, bq // chunk, some_rows)
        else:
            some_rows(rows)

    if mask.kind == "none":
        block(n_tiles, False, 0)
    elif mask.kind == _SW:
        _each_chunk_its_tiles(block, mask, live_step, q_first, bq, chunk,
                              k_first, n_tiles, tile_k, unmasked=False)
    else:
        # the mask costs this kernel under 1% (the VPU has the room beside
        # three products), a second copy of the body costs code
        _each_kind_of_block(
            block, n_tiles,
            *_kv_tiles_seen(mask, q_first, bq, k_first, n_tiles, tile_k),
            unmasked=False)

    @pl.when(last_step())
    def _():
        # ds was left without the logit scale: once here, on [block_q, d]
        dq_ref[0, 0] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                    mask, scale, tile_q, blocks, pairs, group):
    """Scores transposed, keys down the rows and queries along the lanes:
    every product is in a form the MXU takes as it is (k q^T, v do^T, p^T
    do, ds^T q), and the row statistics come in as lane-dense rows.  A
    resident block of keys streams the query blocks of every q head of its
    ``group`` between its first step and its last (the index maps hand it a
    member's q, do, lse and delta; the body asks only which block of rows a
    step is), so dk and dv are the group's sums, in float32 until the one
    cast."""
    bk = k_ref.shape[2]
    n_tiles = q_ref.shape[2] // tile_q
    i, resident, first_step, last_step = _grid_step(offs_ref, pairs, group)
    if mask.kind == _SW:
        named, live_step = _window_q_block(
            mask, offs_ref[1] + resident() * bk, bk, offs_ref[0],
            blocks, q_ref.shape[2], i)
        q_first = offs_ref[0] + named * q_ref.shape[2]
    else:
        q_first = offs_ref[0] + i * q_ref.shape[2]
    k_first = offs_ref[1] + resident() * bk
    k_scale, s_scale = _scale_parts(scale, k_ref.dtype)

    @pl.when(first_step())
    def _():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def tile(c):
        """dk's and dv's share of query tile ``c`` of the block."""
        rows = _chunk(c, tile_q)
        kb = k_ref[0, 0]
        if k_scale is not None:
            kb = kb * k_scale
        qc = q_ref[0, 0, rows, :]
        doc = do_ref[0, 0, rows, :]
        st = lax.dot_general(kb, qc, _NT, preferred_element_type=jnp.float32)
        if s_scale is not None:
            st = st * s_scale
        pt = jnp.exp(st - lse_ref[0, 0, c])
        if mask.kind != "none":
            # on every tile: the mask costs this kernel nothing measurable
            # (0.2%), a second body is code.  After the exp: a select drops
            # what overflowed where masked
            pt = jnp.where(_seen(mask, (bk, tile_q), q_first, k_first, c,
                                 tile_q, keys_down=True), pt, 0.0)
        dv_acc_ref[:] += lax.dot_general(
            pt.astype(doc.dtype), doc, _NN,
            preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[0, 0], doc, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0, c])
        dk_acc_ref[:] += lax.dot_general(
            dst.astype(qc.dtype), qc, _NN,
            preferred_element_type=jnp.float32)

    # the query tiles that see no key of the block are skipped
    lo, hi = _q_tiles_seen(mask, k_first, bk, q_first, n_tiles, tile_q)
    if mask.kind == _SW:
        # a step past the last live block was handed that block again
        hi = jnp.where(live_step, hi, lo)
    _loop(lo, hi, tile)

    @pl.when(last_step())
    def _():
        dk_ref[0, 0] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _mha_bwd_dq(q, k, v, do, lse, delta, offs, *, mask, block_q, block_k,
                interpret, static_offs=None, **kw):
    """lse/delta ``[b,h,sq,1]``."""
    _count_tiles("dq", static_offs, q, k, v, block_q, block_k, mask)
    return _dq_call(q, k, v, do, lse, delta, offs, mask=mask,
                    block_q=block_q, block_k=block_k, static_offs=static_offs,
                    interpret=_resolve_interpret(interpret), **kw)


@_jit_kernel
def _dq_call(q, k, v, do, lse, delta, offs, *, mask, scale, block_q,
             block_k, interpret, out_dtype=jnp.float32, static_offs=None):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_q, tile_k, block_k, steps, chunk = _kv_grid(
        sq, sk, block_q, block_k, mask, static_offs)
    offs, grid, pairs, resident, streamed = _grid(
        offs, static_offs,
        _pair_table(_kv_blocks_seen, steps, mask, sq, sk, block_q, block_k,
                    static_offs),
        (b, h, sq // block_q, steps), _kv_block(mask, block_q, block_k, sk))
    q_index = _at(resident, 0)
    kv_index = _at(streamed, 0, head=_kv_head(_kv_group(q, k, v)))
    kernel = functools.partial(_bwd_dq_kernel, mask=mask, scale=scale,
                               tile_k=tile_k, blocks=sk // block_k,
                               chunk=chunk, pairs=pairs)
    q_block, k_block, row = ((1, 1, block_q, d), (1, 1, block_k, d),
                             (1, 1, block_q, 1))
    do_block, v_block = (1, 1, block_q, dv), (1, 1, block_k, dv)
    return _launch(
        DQ_KERNEL, kernel, offs, grid,
        ins=[(q, q_block, q_index), (k, k_block, kv_index),
             (v, v_block, kv_index), (do, do_block, q_index),
             (lse, row, q_index), (delta, row, q_index)],
        outs=[((b, h, sq, d), out_dtype, q_block, q_index)],
        scratch=[(block_q, d)], interpret=interpret)[0]


def _mha_bwd_dkv(q, k, v, do, lse, delta, offs, *, mask, block_q, block_k,
                 interpret, static_offs=None, **kw):
    """lse/delta: one float32 a query row, in any shape; the kernel reads
    them as rows of ``tile_q`` lanes.  Returns ``(dk [b,hk,sk,d], dv
    [b,hk,sk,dv])`` at k's and v's own head count, each the sum over the q
    heads of its group."""
    _count_tiles("dkv", static_offs, q, k, v, block_q, block_k, mask)
    return _dkv_call(q, k, v, do, lse, delta, offs, mask=mask,
                     block_q=block_q, block_k=block_k, static_offs=static_offs,
                     interpret=_resolve_interpret(interpret), **kw)


@_jit_kernel
def _dkv_call(q, k, v, do, lse, delta, offs, *, mask, scale, block_q,
              block_k, interpret, out_dtype=jnp.float32, static_offs=None):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    group = _kv_group(q, k, v)
    tile_q, block_q, block_k, steps = _q_grid(sq, sk, block_q, block_k,
                                              mask, static_offs)
    n_tiles = block_q // tile_q
    lse, delta = (x.reshape(b, h, sq // tile_q, 1, tile_q)
                  for x in (lse, delta))

    def first_seen(b_, h_, jk, i, offs):
        if group > 1:
            # the rectangle takes a block's steps once a member of the group
            i = lax.rem(i, steps)
        # a grid step whose query rows all lie before the kv block computes
        # nothing: it names the first block that does, and nothing is
        # fetched
        if mask.kind == "causal":
            first = _clip(offs[1] + jk * block_k - offs[0],
                          0, sq - 1) // block_q
            i = jnp.maximum(i, first)
        elif mask.kind == _SW:
            # nor do the rows past the last key's window: step i of the few
            # a block of keys takes names the i-th block that sees it
            i, _ = _window_q_block(mask, offs[1] + jk * block_k, block_k,
                                   offs[0], sq // block_q, block_q, i)
        elif mask.kind == _BD:
            n = mask.noised // block_q
            i = _nearest_live(i, [tuple(part * n + t for t in _q_tiles_seen(
                mask, jk * block_k, block_k, part * mask.noised, n, block_q))
                for part in range(2)])
        return i

    offs, grid, pairs, resident, streamed = _grid(
        offs, static_offs,
        _pair_table(_q_blocks_seen, steps, mask, sq, sk, block_q, block_k,
                    static_offs, group),
        (b, h // group, sk // block_k, group * steps), first_seen)
    # the grid runs over the kv heads; a step's q head is its member of the
    # kv head's group (the table's fourth row; on the rectangle the pass
    # the step is in)
    def member_head(b_, h_, *step):
        *step, offs = step
        member = (offs[2 + 3 * pairs + step[0]] if pairs
                  else lax.div(step[1], steps))
        return h_ * group + member

    q_head = member_head if group > 1 else None
    kv_index, q_index, row_index = (
        _at(resident, 0), _at(streamed, 0, head=q_head),
        _at(streamed, 0, 0, head=q_head))
    kernel = functools.partial(_bwd_dkv_kernel, mask=mask, scale=scale,
                               tile_q=tile_q, blocks=sq // block_q,
                               pairs=pairs, group=group)
    q_block, k_block, rows = ((1, 1, block_q, d), (1, 1, block_k, d),
                              (1, 1, n_tiles, 1, tile_q))
    do_block, v_block = (1, 1, block_q, dv), (1, 1, block_k, dv)
    return _launch(
        DKV_KERNEL, kernel, offs, grid,
        ins=[(q, q_block, q_index), (k, k_block, kv_index),
             (v, v_block, kv_index), (do, do_block, q_index),
             (lse, rows, row_index), (delta, rows, row_index)],
        outs=[((b, h // group, sk, d), out_dtype, k_block, kv_index),
              ((b, h // group, sk, dv), out_dtype, v_block, kv_index)],
        scratch=[(block_k, d), (block_k, dv)], interpret=interpret)


# ---------------------------------------------------------------------------
# ring building blocks (dynamic offsets, [b,h,s,d] layout)
# ---------------------------------------------------------------------------


def mha_partial(q, k, v, q_offset, kv_offset, *, causal, scale,
                block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=None):
    """Unnormalized streaming triple ``(o[f32], m, l)`` for one q-shard ×
    kv-shard pair; offsets are *global positions* and may be traced.
    m/l come back ``[b,h,sq,1]`` so they broadcast against ``o``; a row
    that sees no key has ``l`` 0 and ``m`` :data:`M_INIT`."""
    return _mha_fwd(
        q, k, v, _offsets(q_offset, kv_offset), mask=_as_mask(causal),
        scale=scale,
        block_q=block_q, block_k=block_k, normalize=False,
        interpret=interpret,
        static_offs=_static_offsets(q_offset, kv_offset),
    )


def mha_bwd_dq(q, k, v, do, lse, delta, q_offset, kv_offset, *, causal,
               scale, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
               interpret=None):
    """dq (f32) contribution of one kv shard; lse/delta are ``[b,h,sq,1]``."""
    return _mha_bwd_dq(
        q, k, v, do, lse, delta, _offsets(q_offset, kv_offset),
        mask=_as_mask(causal), scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
        static_offs=_static_offsets(q_offset, kv_offset),
    )


def mha_bwd_dkv(q, k, v, do, lse, delta, q_offset, kv_offset, *, causal,
                scale, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                interpret=None):
    """(dk, dv) (f32) contributions of one q shard to one kv shard."""
    return _mha_bwd_dkv(
        q, k, v, do, lse, delta, _offsets(q_offset, kv_offset),
        mask=_as_mask(causal), scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
        static_offs=_static_offsets(q_offset, kv_offset),
    )


# ---------------------------------------------------------------------------
# local flash attention with custom VJP
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_fn(mask, scale, block_q, block_k, interpret, static_offs):
    kw = dict(mask=mask, scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret, static_offs=static_offs)

    @jax.custom_vjp
    def f(q, k, v, offs):
        o, _, _ = _mha_fwd(q, k, v, offs, normalize=True, **kw)
        return o

    def fwd(q, k, v, offs):
        o, m, l = _mha_fwd(q, k, v, offs, normalize=True, **kw)
        # kept [b,h,sq]: a [b,h,sq,1] float32 array pads every row to a
        # tile of 128 lanes in HBM
        with jax.named_scope(LAYOUT_SCOPE):
            lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
        # what the forward kernel wrote and the backward kernels read: a
        # checkpoint whose policy saves these names runs the kernel once
        o = checkpoint_name(o, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        _count_residuals("flash", o, lse)
        return o, (*_named_inputs(q, k, v), o, lse, offs)

    def bwd(res, do):
        q, k, v, o, lse, offs = res
        with jax.named_scope(LAYOUT_SCOPE):
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1)
            lse_column, delta_column = lse[..., None], delta[..., None]
        dq = _mha_bwd_dq(q, k, v, do, lse_column, delta_column, offs,
                         out_dtype=q.dtype, **kw)
        dk, dv = _mha_bwd_dkv(q, k, v, do, lse, delta, offs,
                              out_dtype=k.dtype, **kw)
        return (dq, dk, dv.astype(v.dtype),
                np.zeros(offs.shape, dtype=jax.dtypes.float0))

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, *, causal: bool = False,
                    mask: Optional[Mask] = None,
                    scale: Optional[float] = None,
                    q_offset=0, kv_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention over local shards, differentiable end to end.

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]`` (the model-facing layout
        used throughout :mod:`horovod_tpu.parallel`); v's head size may
        differ from q's and k's.  k and v may have fewer heads than q
        (grouped-query attention): ``hk`` of them with q's ``h`` a multiple
        (a ``ValueError`` otherwise), and q head ``i`` attends kv head ``i
        // (h // hk)``.  Nothing is repeated in HBM: the kernels find a q
        head's kv head by index map, and the gradients of k and v come back
        at ``hk`` heads, summed over each group inside the dkv kernel.
      causal: apply causal masking in global positions
        (``q_offset + i >= kv_offset + j``).
      mask: a :class:`Mask` in ``causal``'s place:
        :func:`sliding_window_mask` (positions moved by the offsets, as
        the causal case's) or :func:`block_diffusion_mask` (which takes no
        offsets: the rows are the whole doubled sequence).
      scale: logit scale, default ``1/sqrt(head_dim)``.
      q_offset, kv_offset: global position of element 0 of the q / kv
        shards (used by sequence-parallel callers).
      block_q, block_k: rows and columns of one score tile; by default
        :func:`default_blocks` of the head size.

    Returns attention output in ``q``'s dtype, ``[batch, seq, heads, v's
    head_dim]``.
    """
    mask = _as_mask(causal) if mask is None else mask
    static_offs = _static_offsets(q_offset, kv_offset)
    if mask.kind == _BD and static_offs != (0, 0):
        raise ValueError("a block-diffusion mask takes no offsets")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    default_q, default_k = default_blocks(q.shape[-1], mask)
    fn = _flash_fn(mask, float(scale), int(block_q or default_q),
                   int(block_k or default_k), _resolve_interpret(interpret),
                   static_offs)
    with jax.named_scope(LAYOUT_SCOPE):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
    o = fn(qt, kt, vt, _offsets(q_offset, kv_offset))
    with jax.named_scope(LAYOUT_SCOPE):
        return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def softmax_attention(q, k, v, *, causal: bool = False,
                      scale: Optional[float] = None):
    """Plain (materialized) softmax attention in ``[b,s,h,d]`` layout —
    the XLA-fused reference path the flash kernels are checked against,
    shared by the Ulysses local step and the benchmarks' --attn xla
    mode.  XLA fuses the chain; memory is O(s^2)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    sl = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = q.shape[1]
        pos = jnp.arange(s)
        sl = jnp.where((pos[:, None] >= pos[None, :])[None, None], sl,
                       -jnp.inf)
    p = jax.nn.softmax(sl, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)



# ---------------------------------------------------------------------------
# what a recomputed layer may keep besides ``FLASH_OUT`` and ``FLASH_LSE``
# ---------------------------------------------------------------------------
# Down here, and the ``fwd`` rule's use of it on the line that was there, so
# that no line moves on the way from a caller to a kernel: a Mosaic body
# carries the source lines of its call stack, and a model that keeps
# nothing lowers to the bytes it had.

# The backward kernels' other residuals: the forward's own inputs as the
# kernels take them (q ``[b, h, s, d]``, k and v ``[b, hk, s, d]`` at their
# own head count, after the caller's norms and rotary and after the swap).
# A checkpoint that saves these too does none of that again
# (``models/recompute.py`` ranks them against its budget).
FLASH_Q = "hvd_flash_q"
FLASH_K = "hvd_flash_k"
FLASH_V = "hvd_flash_v"


def _named_inputs(q, k, v):
    return (checkpoint_name(q, FLASH_Q), checkpoint_name(k, FLASH_K),
            checkpoint_name(v, FLASH_V))


def residual_bytes(b: int, h: int, s: int, dv: int, itemsize: int) -> int:
    """Bytes a differentiated call at these sizes holds from the forward
    pass to the backward beyond its inputs: ``o`` and ``lse`` (what
    :func:`_count_residuals` counts) and the forward kernel's two row
    statistics, ``[b, h, s, 1]`` float32 that XLA pads to 128 lanes and
    makes ``lse`` from only just before the backward kernels (2.1 GB of
    ``sdar-bd4-8k``'s step: PERF.md section 7)."""
    return b * h * s * (dv * itemsize + 4 + 2 * 128 * 4)
