"""Chip smoke: the compiled training path, once, on every chip JAX sees.

The quickest proof that the system still starts on the accelerator.  One
process, the entry points every user calls (``hvd.init``,
``init_train_state``, ``shard_batch``, ``make_train_step``), two models
at full width, each for one compile plus three ``k=1`` steps on a re-fed
batch of seeded random data:

* GPT-2-small (12 layers, d 768, 12 heads, vocab 50257), bf16, 8
  sequences x 1024 tokens per chip, ``optax.adam``, default attention —
  so the three Pallas flash kernels compile;
* ResNet-50, bf16, 224x224, batch 128 per chip, SGD momentum 0.9, through
  ``examples.synthetic_benchmark.run``, the function ``bench.py`` calls.

It checks: the device is a TPU; ``flash_attention`` agrees with
``softmax_attention`` on the chip (forward and gradients, and with its
offsets traced), also at head size 256; under the block-diffusion mask
the kernels agree with a dense-mask float32 softmax at ``[1, 16384, 32,
128]``; at latent attention's head sizes (q and k ``[1, 8192, 32, 192]``, v
``[1, 8192, 32, 128]``) with a dense float32 causal softmax; the chunked
gated delta rule
agrees with its token-by-token recurrence at ``[1, 2048, 32, 128]``, and
the chunked state-space scan with its own at ``[1, 8192, 64, 64]`` (a state
of 128 in 8 groups), with the scan's time and share of its roofline;
LFM2's toy (a gated short convolution or attention, then a dense SwiGLU or
routed experts, a tied head) takes three steps through the step builder,
with the time of the convolution's gates and taps alone at ``[2, 8192,
2048]``; on
more than one chip, ring attention's Pallas variant
agrees with it too (gradients over the whole ring); the GPT step's
compiled module holds Mosaic custom calls; every loss is finite and the
third is below the first; and, on more than one chip, that the batch is
sharded one shard per device, the state replicated on all of them, the
step holds an all-reduce, and the first-step loss equals the same global
batch's loss on a one-device world.

Any failed check raises, so the exit code is non-zero and no result line
is printed.  The timings it prints are a smoke's, never benchmark numbers.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import sys
import time
from importlib import metadata

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

GPT_SEQ = 1024
GPT_SEQS_PER_CHIP = 8
RESNET_BATCH_PER_CHIP = 128
#: flash vs softmax reference, bf16 inputs: max |a - b| / max |b|.  bf16
#: keeps 8 significant bits (2^-8 = 0.4%) and the two paths round the
#: probabilities at different points, so a few percent of the largest
#: element is the agreement bf16 can give.
FLASH_TOL = 3e-2
#: chunked gated delta rule (bf16 operands, float32 state and decays) vs the
#: float32 recurrence on the same bf16-rounded inputs: max |a - b| / max |b|.
#: The chunked form rounds T, U_hat, W and the state it reads to bf16 once
#: a chunk, each 2^-8; the errors of a chunk's products add up in the
#: output like the flash kernels' do, so the same few percent.
SCAN_TOL = 3e-2
#: n-chip vs one-device first-step loss (absolute; the loss is ~11):
#: the same rows through programs tiled for different batch shapes
LOSS_TOL = 1e-2


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes() -> int:
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.local_devices())


def cache_entries(path: str) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def flash_phase(b: int = 2, s: int = GPT_SEQ, h: int = 12,
                d: int = 64) -> None:
    """flash_attention vs softmax_attention at one shape on the chip, with
    the tiles the models call it with at that head size."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.qwen3_next import flash_blocks
    from horovod_tpu.ops import flash_attention as fa

    softmax_attention = fa.softmax_attention
    flash_attention = functools.partial(fa.flash_attention,
                                        **flash_blocks(d))

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                  for kk in keys)

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(
            (attn(q, k, v, causal=True) * w).astype(jnp.float32))

    got = {}
    for name, fn in (("flash", flash_attention), ("ref", softmax_attention)):
        out = jax.jit(lambda q, k, v: fn(q, k, v, causal=True))(q, k, v)
        grads = jax.jit(jax.grad(loss_of(fn), argnums=(0, 1, 2)))(q, k, v)
        got[name] = [np.asarray(a, np.float32) for a in (out, *grads)]
    errs = {}
    for label, a, b_ in zip(("out", "dq", "dk", "dv"),
                            got["flash"], got["ref"]):
        check(np.isfinite(a).all(), f"flash {label} is not finite")
        errs[label] = float(np.abs(a - b_).max() / np.abs(b_).max())
        check(errs[label] <= FLASH_TOL,
              f"flash {label} differs from softmax_attention by "
              f"{errs[label]:.3g} of its largest element (> {FLASH_TOL})")
    # the same call with its offsets traced, as ring attention makes it:
    # the tile's kind is then decided on the chip, by the same kernels
    traced = jax.jit(lambda q, k, v, at: flash_attention(
        q, k, v, causal=True, q_offset=at, kv_offset=at))(
            q, k, v, jnp.int32(0))
    errs["traced_offsets"] = float(
        np.abs(np.asarray(traced, np.float32) - got["flash"][0]).max())
    check(errs["traced_offsets"] == 0.0,
          "flash with traced offsets differs from the static call")
    report("flash_vs_reference", shape=[b, s, h, d], dtype="bfloat16",
           causal=True, tolerance=FLASH_TOL, rel_max_err=errs)


def _flash_against_dense(what: str, flash, seen, q, k, v, w) -> dict:
    """``flash(q, k, v)`` against the float32 softmax over the pairs ``seen``
    allows (``[rows, rows]`` booleans), on the same bf16-rounded inputs, a
    head at a time (one head's ``16384 x 16384`` scores are 1 GB): forward
    and all three gradients of ``sum(out * w)``.  Returns the largest
    errors, each relative to the reference's largest element."""
    import jax
    import jax.numpy as jnp

    scale = q.shape[-1] ** -0.5

    def dense(q, k, v):
        def head(qkv):
            qh, kh, vh = (x.astype(jnp.float32) for x in qkv)   # [rows, d]
            s = jnp.dot(qh, kh.T, precision="highest") * scale
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.dot(p, vh, precision="highest")
        per_head = tuple(jnp.moveaxis(x[0], 1, 0) for x in (q, k, v))
        out = jax.lax.map(jax.checkpoint(head), per_head)      # [h, rows, dv]
        return jnp.moveaxis(out, 0, 1)[None]

    got = {}
    for name, fn in (("flash", flash), ("ref", dense)):
        out, grads = jax.jit(lambda q, k, v: (fn(q, k, v), jax.grad(
            lambda q, k, v: jnp.sum((fn(q, k, v) * w).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)))(q, k, v)
        got[name] = [np.asarray(a, np.float32) for a in (out, *grads)]
    errs = {}
    for label, a, b_ in zip(("out", "dq", "dk", "dv"),
                            got["flash"], got["ref"]):
        check(a.shape == b_.shape, f"{what} flash {label} is {a.shape}")
        check(np.isfinite(a).all(), f"{what} flash {label} is not finite")
        errs[label] = float(np.abs(a - b_).max() / np.abs(b_).max())
        check(errs[label] <= FLASH_TOL,
              f"{what} flash {label} differs from the dense float32 "
              f"softmax by {errs[label]:.3g} of its largest element "
              f"(> {FLASH_TOL})")
    return errs


def block_diffusion_phase(block: int = 4, noised: int = 8192, h: int = 32,
                          d: int = 128) -> None:
    """The flash kernels under the block-diffusion mask at the shape the
    ``sdar-bd4-8k`` cell runs them, ``[1, 2 x 8192, 32, 128]`` bfloat16,
    against the dense-mask float32 softmax, forward and all three
    gradients."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    rows = 2 * noised
    mask = fa.block_diffusion_mask(block, noised)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, w = (jax.random.normal(kk, (1, rows, h, d), jnp.bfloat16)
                  for kk in keys)
    i = jnp.arange(rows)
    c, g = i // noised, (i % noised) // block
    seen = ((c[None, :] == 1) & (g[None, :] < g[:, None] + c[:, None])) | (
        (c[:, None] == 0) & (c[None, :] == 0) & (g[None, :] == g[:, None]))
    errs = _flash_against_dense(
        "block-diffusion",
        lambda q, k, v: fa.flash_attention(q, k, v, mask=mask),
        seen, q, k, v, w)
    report("flash_block_diffusion_vs_dense_mask", shape=[1, rows, h, d],
           dtype="bfloat16", block=block, tolerance=FLASH_TOL,
           tiles=fa.tile_census(rows, rows, *fa.default_blocks(d), mask),
           rel_max_err=errs)


def latent_attention_phase(s: int = 8192, h: int = 32, dk: int = 192,
                           dv: int = 128) -> None:
    """The flash kernels where v's head size is not q.k's, at the shape
    the ``kanana2-8k`` cell runs them (q and k ``[1, 8192, 32, 192]``, v
    ``[1, 8192, 32, 128]``, bfloat16, causal), against the dense float32
    causal softmax, forward and all three gradients."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k = (jax.random.normal(kk, (1, s, h, dk), jnp.bfloat16)
            for kk in keys[:2])
    v, w = (jax.random.normal(kk, (1, s, h, dv), jnp.bfloat16)
            for kk in keys[2:])
    errs = _flash_against_dense(
        "latent",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], q, k, v, w)
    report("flash_latent_vs_dense", qk=[1, s, h, dk], v=[1, s, h, dv],
           dtype="bfloat16", causal=True, tolerance=FLASH_TOL,
           blocks=list(fa.default_blocks(dk)), rel_max_err=errs)


def _ms_a_call(fn, *args, calls: int = 10) -> float:
    """Milliseconds a call of ``fn(*args)`` by the host's clock, over
    ``calls`` calls after a warm one, ended by ``block_until_ready``."""
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / calls


def _flash_kernels_alone(q, k, v, do, mask, block_q, block_k,
                         static_offs=(0, 0), repeats: int = 1):
    """Each of the three flash kernels alone on ``[b, h, s, d]`` operands at
    offsets 0, traced anew (the sweeps change what the launchers read
    while they trace): ``(ms a call of forward, dq and dkv, the least of
    ``repeats`` readings; what they returned)``."""
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    for launcher in (fa._fwd_call, fa._dq_call, fa._dkv_call):
        launcher.clear_cache()
    offs = fa._offsets(0, 0)
    kw = dict(mask=mask, scale=q.shape[-1] ** -0.5, block_q=block_q,
              block_k=block_k, interpret=None, static_offs=static_offs)
    o, m, l = fa._mha_fwd(q, k, v, offs, normalize=True, **kw)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    back = (q, k, v, do, lse, delta, offs)
    calls = {
        "fwd": lambda: fa._mha_fwd(q, k, v, offs, normalize=True, **kw)[0],
        "dq": lambda: fa._mha_bwd_dq(*back, out_dtype=q.dtype, **kw),
        "dkv": lambda: fa._mha_bwd_dkv(*back[:4], lse[..., 0],
                                       delta[..., 0], offs,
                                       out_dtype=q.dtype, **kw)}
    ms = {name: min(_ms_a_call(call) for _ in range(repeats))
          for name, call in calls.items()}
    return ms, [np.asarray(x, np.float32) for x in (
        calls["fwd"](), calls["dq"](), *calls["dkv"]())]


def sliding_window_phase(window: int = 1024, s: int = 16384, h: int = 32,
                         d: int = 128) -> None:
    """The flash kernels under the sliding-window mask at the shape the
    ``mellum2-16k`` cell's window layers run them, ``[1, 16384, 32, 128]``
    bfloat16 with a window of 1024, against the dense-mask float32 softmax,
    forward and all three gradients; the tiles a caller gets who names
    none, the grid steps the three kernels launch and how many of them are
    live, and a layer's call with its layout swaps, forward and forward and
    backward."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    mask = fa.sliding_window_mask(window)
    blocks = fa.default_blocks(d, mask)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, w = (jax.random.normal(kk, (1, s, h, d), jnp.bfloat16)
                  for kk in keys)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask)

    errs = _flash_against_dense(
        "sliding-window", flash, (j <= i) & (i - j < window), q, k, v, w)
    both = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum((flash(q, k, v) * w).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    report("flash_sliding_window_vs_dense_mask", shape=[1, s, h, d],
           dtype="bfloat16", window=window, tolerance=FLASH_TOL,
           blocks=list(blocks), tiles=fa.tile_census(s, s, *blocks, mask),
           grid_steps_a_head=fa.grid_census(s, s, *blocks, mask),
           layer_fwd_ms=_ms_a_call(jax.jit(flash), q, k, v),
           layer_fwd_bwd_ms=_ms_a_call(both, q, k, v),
           rel_max_err=errs)


def sliding_window_sweep(window: int = 1024, s: int = 16384, h: int = 32,
                         d: int = 128, rows=(256, 512, 1024),
                         keys=(256, 512), tiles=(1, 2, 4),
                         row_tiles=(1,)) -> None:
    """The sweep ``ops/flash_attention.default_blocks`` takes its rule under
    a window from (not part of ``main``: ``python -c "import chip_smoke;
    chip_smoke.sliding_window_sweep()"`` on the chip gives its docstring's
    first table, ``sliding_window_sweep(rows=(512,), keys=(512,),
    row_tiles=(1, 2, 4))`` the second): each of the three
    kernels alone at ``[1, h, s, d]`` bfloat16 under a window of ``window``,
    over rows a query tile x keys a tile x tiles a grid step x tiles of
    rows forward's and dq's resident block holds, in us an
    *allowed* 512 x 512 pair-tile (``s window - window (window - 1) / 2``
    allowed pairs a head), with the grid steps each launches; and the whole
    grid's steps at the causal tiles, whose difference from the fitted grid
    is the cost of a step that is visited to do nothing."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.ops import flash_attention as fa

    if not hvd.is_initialized():
        hvd.init(platform="tpu")
    mask = fa.sliding_window_mask(window)
    q, k, v, do = (jax.random.normal(kk, (1, h, s, d), jnp.bfloat16)
                   for kk in jax.random.split(jax.random.PRNGKey(5), 4))
    allowed_tiles = h * (s * window - window * (window - 1) // 2) / 512 ** 2
    fitted = (fa._tiles_per_step, fa._window_steps, fa._row_tiles)

    def kernels(block_q, block_k, static_offs=(0, 0)):
        return _flash_kernels_alone(q, k, v, do, mask, block_q, block_k,
                                    static_offs)

    reference = None
    try:
        for block_q, block_k, n, held in itertools.product(
                rows, keys, tiles, row_tiles):
            fa._tiles_per_step = lambda seq, tile, mask, n=n: n
            fa._row_tiles = lambda sq, tile, held=held: held
            ms, got = kernels(block_q, block_k)
            reference = reference or got
            report("sliding_window_sweep", rows=block_q, keys=block_k,
                   tiles_a_step=n, row_tiles=held,
                   us_an_allowed_tile={name: t * 1e3 / allowed_tiles
                                       for name, t in ms.items()},
                   ms=ms, grid_steps_a_head=fa.grid_census(
                       s, s, block_q, block_k, mask),
                   largest_difference_from_the_first=[
                       float(np.abs(a - b).max())
                       for a, b in zip(got, reference)])
        # the causal tiles over every block there is, as before the grid was
        # fitted, and over the fitted grid: the steps between cost the rest
        fa._tiles_per_step = lambda seq, tile, mask: fa.TILES_PER_STEP
        fa._row_tiles = lambda sq, tile: 1
        fit, _ = kernels(fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
        steps = fa.grid_census(s, s, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K,
                               mask)
        fa._window_steps = lambda mask, resident, block, n: n
        whole, _ = kernels(fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K, None)
        every = fa.grid_census(s, s, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K,
                               mask, None, None)
        report("sliding_window_idle_steps", whole_grid_ms=whole,
               fitted_grid_ms=fit, us_an_idle_step={
                   name: (whole[name] - fit[name]) * 1e3 / h
                   / (every[name]["launched"] - steps[name]["launched"])
                   for name in whole})
    finally:
        fa._tiles_per_step, fa._window_steps, fa._row_tiles = fitted
        for launcher in (fa._fwd_call, fa._dq_call, fa._dkv_call):
            launcher.clear_cache()


def flash_grid_phase(cells=None) -> None:
    """Each of the three flash kernels alone on the flattened grid of live
    (resident block, streamed block) pairs and on the rectangle it replaced
    (``MAX_PAIRS`` 0: the launchers then take the parent's path, body for
    body), in one process, at the shapes the benchmark's cells call them
    with (``cells``: name, ``(b, h, s)``, q's and v's head size, mask): ms
    a call, the steps each grid launches a head
    (``grid_census``), what a step the rectangle visited to do nothing
    cost, and that the two grids give the same bits.  Not part of
    ``main``: ``python chip_smoke.py flash_grid_phase``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    cells = cells or (
        ("sdar-bd4-8k", (1, 32, 16384), 128, 128,
         fa.block_diffusion_mask(4, 8192)),
        ("gpt2s-16k", (1, 12, 16384), 64, 64, fa.CAUSAL),
        ("mellum2-16k full layer", (1, 32, 16384), 128, 128, fa.CAUSAL),
        ("kanana2-8k", (1, 32, 8192), 192, 128, fa.CAUSAL),
        ("gpt2s-4k", (2, 12, 4096), 64, 64, fa.CAUSAL),
        ("gpt2s-1k", (8, 12, 1024), 64, 64, fa.CAUSAL),
    )
    most = fa.MAX_PAIRS
    try:
        for cell, (b, h, s), d, dv, mask in cells:
            keys = jax.random.split(jax.random.PRNGKey(7), 4)
            q, k = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                    for kk in keys[:2])
            v, do = (jax.random.normal(kk, (b, h, s, dv), jnp.bfloat16)
                     for kk in keys[2:])
            blocks = fa.default_blocks(d, mask)
            fa.MAX_PAIRS = most
            flat, got = _flash_kernels_alone(q, k, v, do, mask, *blocks,
                                             repeats=3)
            steps = fa.grid_census(s, s, *blocks, mask)
            fa.MAX_PAIRS = 0
            whole, want = _flash_kernels_alone(q, k, v, do, mask, *blocks,
                                               repeats=3)
            every = fa.grid_census(s, s, *blocks, mask)
            for a, b_ in zip(got, want):
                check(np.array_equal(a, b_),
                      f"{cell}: the flattened grid and the rectangle "
                      "differ")
            idle = {name: every[name]["launched"] - steps[name]["launched"]
                    for name in flat}
            report("flash_grid", cell=cell, shape=[b, h, s, d, dv],
                   mask=mask.label, blocks=list(blocks),
                   flattened_ms=flat, rectangle_ms=whole,
                   flattened_steps_a_head=steps,
                   rectangle_steps_a_head=every,
                   us_an_idle_step={
                       name: (whole[name] - flat[name]) * 1e3
                       / (b * h * idle[name]) if idle[name] else None
                       for name in flat})
    finally:
        fa.MAX_PAIRS = most
        for launcher in (fa._fwd_call, fa._dq_call, fa._dkv_call):
            launcher.clear_cache()


def scan_phase() -> None:
    """The gated delta rule's Pallas kernels vs the recurrence itself,
    forward and gradients, at the head counts and sizes of Qwen3-Next's
    DeltaNet layers (16 key heads serve 32 value heads of 128): the
    kernels compiled by Mosaic, the state in VMEM across four blocks of
    eight chunks."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.gated_delta import (
        gated_delta_recurrence, gated_delta_rule,
    )

    b, s, hk, h, d = 1, 2048, 16, 32, 128
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k = (jax.random.normal(kk, (b, s, hk, d), jnp.float32)
            for kk in keys[:2])
    v, w = (jax.random.normal(kk, (b, s, h, d), jnp.float32)
            for kk in keys[2:4])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, k, v, w = (x.astype(jnp.bfloat16) for x in (q, k, v, w))
    # log decays from -0.02 to -12 a token, as A_log's uniform(0, 16) gives
    g = -jnp.exp(jax.random.uniform(keys[4], (b, s, h), minval=-4.0,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, s, h)))

    def loss_of(fn):
        return lambda q, k, v, g, beta: jnp.sum(
            fn(q, k, v, g, beta).astype(jnp.float32)
            * w.astype(jnp.float32))

    got = {}
    for name, fn in (("chunked", gated_delta_rule),
                     ("recurrence", gated_delta_recurrence)):
        with jax.default_matmul_precision(
                "highest" if name == "recurrence" else "default"):
            out = jax.jit(fn)(q, k, v, g, beta)
            grads = jax.jit(jax.grad(loss_of(fn), argnums=(0, 1, 2, 3, 4)))(
                q, k, v, g, beta)
        got[name] = [np.asarray(a, np.float32) for a in (out, *grads)]
    errs = {}
    for label, a, b_ in zip(("out", "dq", "dk", "dv", "dg", "dbeta"),
                            got["chunked"], got["recurrence"]):
        check(np.isfinite(a).all(), f"chunked scan {label} is not finite")
        errs[label] = float(np.abs(a - b_).max() / np.abs(b_).max())
        check(errs[label] <= SCAN_TOL,
              f"chunked scan {label} differs from the recurrence by "
              f"{errs[label]:.3g} of its largest element (> {SCAN_TOL})")
    report("scan_vs_recurrence", shape=[b, s, hk, h, d], dtype="bfloat16",
           chunk=64, tolerance=SCAN_TOL, rel_max_err=errs)


def ssd_phase(s: int = 8192, h: int = 64, p: int = 64, g: int = 8,
              n: int = 128, chunk: int = 128, per_step=None) -> None:
    """The chunked state-space scan (``ops/ssd.py``) alone at the shape
    ``nemotron3-8k``'s Mamba-2 blocks run it, ``[1, 8192, 64, 64]`` bf16
    with B and C in 8 groups of 128, in both of its forms: the Pallas
    kernels (what ``ssd`` takes here) and the XLA ops they stand beside
    (``_chunked``: what it takes off a TPU or where the shapes do not
    tile).  Each against the recurrence itself, forward and gradients, and
    its time forward and forward + backward with the share of its roofline
    that is (the recurrence's three ``P x N`` products a head a token and
    x, dt, B, C, y and their gradients once each: what
    ``benchmarks/harness/nemotron_h_parts.scan_train_required`` charges a
    block).  ``per_step``: chunks a grid step to time the kernels at
    beside ``ssd.CHUNKS_PER_STEP`` (:func:`ssd_sweep`)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import metrics
    from horovod_tpu.ops import ssd as ssd_ops
    from horovod_tpu.ops.ssd import ssd, ssd_recurrence
    from horovod_tpu.utils import flops

    keys = jax.random.split(jax.random.PRNGKey(2), 7)
    x, w = (jax.random.normal(kk, (1, s, h, p), jnp.float32).astype(
        jnp.bfloat16) for kk in keys[:2])
    bm, cm = (jax.random.normal(kk, (1, s, g, n), jnp.float32).astype(
        jnp.bfloat16) for kk in keys[2:4])
    # steps log-uniform on [1e-3, 0.1] as the seeded dt_bias gives, rates
    # -1 .. -64 as A_log = log(1..64) gives
    dt = jnp.exp(jax.random.uniform(keys[4], (1, s, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    rate = -jnp.arange(1, h + 1, dtype=jnp.float32)
    skip = jnp.ones((h,), jnp.float32)
    args = (x, dt, rate, bm, cm, skip)

    def loss_of(fn, weight):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                  * weight.astype(jnp.float32))

    def xla(*a):
        with jax.named_scope(ssd_ops.SCAN_SCOPE):
            return ssd_ops._scan(chunk, *a)

    def as_the_model_holds_them(fn):
        """x, B and C arrive as ``[b, s, columns]`` slices of the
        convolution's output and y leaves as ``[b, s, heads p]``: the
        reshapes round the call are then free, as in the model (a 4-d
        array with 64 columns at its end is padded to 128 in HBM, and
        turning it into ``[b, s, 4096]`` is a copy)."""
        def call(x, dt, rate, bm, cm, skip):
            rows = x.shape[:2]
            return fn(x.reshape(*rows, h, p), dt, rate,
                      bm.reshape(*rows, g, n), cm.reshape(*rows, g, n),
                      skip).reshape(*rows, h * p)
        return call

    forms = {"kernels": functools.partial(ssd, chunk=chunk), "xla": xla}
    forms = {k: as_the_model_holds_them(f) for k, f in forms.items()}
    recurrence = as_the_model_holds_them(ssd_recurrence)
    x, w, bm, cm = (a.reshape(1, s, -1) for a in (x, w, bm, cm))
    args = (x, dt, rate, bm, cm, skip)
    # against the recurrence over the first ``checked`` tokens: its
    # gradient keeps a float32 state a token (2 MB: 17 GB at 8192)
    checked = 1024
    short = tuple(a[:, :checked] if a.ndim > 1 else a for a in args)
    got = {}
    for name, fn in (*forms.items(), ("recurrence", recurrence)):
        with jax.default_matmul_precision(
                "highest" if name == "recurrence" else "default"):
            out = jax.jit(fn)(*short)
            grads = jax.jit(jax.grad(loss_of(fn, w[:, :checked]),
                                     argnums=range(6)))(*short)
        got[name] = [np.asarray(a, np.float32) for a in (out, *grads)]
    products = 2.0 * 3 * s * h * p * n
    tensors = s * ((h * p * 2 + 2 * g * n) * 2 + h * 4)
    peak, hbm = flops.require_peak_flops(), flops.hbm_bytes_per_sec()
    least = {"fwd": max(products / peak, tensors / hbm) * 1e3,
             "fwd_bwd": max(3 * products / peak, 3 * tensors / hbm) * 1e3}

    def timed(fn):
        fwd_ms = _ms_a_call(jax.jit(fn), *args)
        both_ms = _ms_a_call(
            jax.jit(jax.grad(loss_of(fn, w), argnums=range(6))), *args)
        return dict(fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
                    fwd_roofline_pct=100 * least["fwd"] / fwd_ms,
                    fwd_bwd_roofline_pct=100 * least["fwd_bwd"] / both_ms)

    by_form = {}
    for name, fn in forms.items():
        errs = {}
        for label, a, b_ in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"),
                                got[name], got["recurrence"]):
            check(np.isfinite(a).all(), f"ssd ({name}) {label} is not finite")
            errs[label] = float(np.abs(a - b_).max() / np.abs(b_).max())
            check(errs[label] <= SCAN_TOL,
                  f"ssd ({name}) {label} differs from the recurrence by "
                  f"{errs[label]:.3g} of its largest element (> {SCAN_TOL})")
        by_form[name] = dict(rel_max_err=errs, **timed(fn))
    by_form["kernels"]["chunks_per_grid_step"] = ssd_ops.CHUNKS_PER_STEP
    swept = {}
    for chunks in per_step or ():
        # read where a call is traced: another count is another program
        ssd_ops.CHUNKS_PER_STEP, ours = chunks, ssd_ops.CHUNKS_PER_STEP
        try:
            swept[chunks] = timed(forms["kernels"])
        except Exception as e:  # noqa: BLE001 — a block Mosaic refuses
            swept[chunks] = str(e)[-300:]
        ssd_ops.CHUNKS_PER_STEP = ours
    paths = {"/".join(labels.values()): int(child.get())
             for labels, child in metrics.SSM_SCAN_CHUNKS.samples()}
    check(any(k.endswith("/mosaic") for k in paths),
          f"ssd took no Mosaic kernel on the chip: {paths}")
    report("ssd_vs_recurrence", shape=[1, s, h, p], groups=g, state=n,
           chunk=chunk, dtype="bfloat16", checked_tokens=checked,
           tolerance=SCAN_TOL, least_ms=least, chunks_traced=paths,
           swept_chunks_per_grid_step=swept, **by_form)


def ssd_sweep() -> None:
    """:func:`ssd_phase` with the kernels timed at 2 and 8 chunks a grid
    step too (not part of ``main``: ``python chip_smoke.py ssd_sweep``)."""
    ssd_phase(per_step=(2, 8))


def lfm2_phase(b: int = 2, s: int = 8192, d: int = 2048,
               taps: int = 3) -> None:
    """LFM2's toy (``models/lfm2.lfm2_tiny``: a dense convolution layer, an
    attention layer, three convolution layers with experts, the tied head)
    through ``init_train_state`` -> ``make_train_step`` for three steps, and
    the gated short convolution's gates and taps alone (``B * x``, the
    causal depthwise taps, ``C * z``: what sits between the operator's two
    products, XLA's) at the shape ``lfm2-8k-b2`` runs them, ``[2, 8192,
    2048]`` bf16 a tensor: time forward and forward + backward with the
    share of the roofline that is (B, C, x in and y out forward; dy, B, x,
    C in and dB, dC, dx out backward, once each: what
    ``benchmarks/harness/lfm2_parts.sconv_gate_train_required`` charges a
    layer), for the ``perf_opt`` that follows."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.gpt import next_token_loss
    from horovod_tpu.models.lfm2 import lfm2_tiny
    from horovod_tpu.models.qwen3_next import causal_depthwise_conv
    from horovod_tpu.training import (init_train_state, make_train_step,
                                      shard_batch)
    from horovod_tpu.utils import flops

    model, opt = lfm2_tiny(), optax.adam(1e-3)
    state = init_train_state(model, opt, jnp.zeros((1, 256), jnp.int32))
    step = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=next_token_loss, optimizer=opt)
    ids = shard_batch(np.random.default_rng(0).integers(
        0, model.vocab_size, (hvd.size(), 256)).astype(np.int32))
    losses = []
    for _ in range(3):
        state, loss = step(state, ids, ids)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"lfm2_tiny loss not finite: {losses}")
    check(losses[2] < losses[0], f"lfm2_tiny loss did not fall: {losses}")

    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    gate_in, gate_out, x, w = (
        jax.random.normal(k, (b, s, d), jnp.float32).astype(jnp.bfloat16)
        for k in keys[:4])
    kernel = (0.02 * jax.random.normal(keys[4], (taps, d))).astype(
        jnp.bfloat16)

    def gates(gate_in, gate_out, x, kernel):
        return gate_out * causal_depthwise_conv(gate_in * x, kernel)

    def loss(*a):
        return jnp.sum(gates(*a).astype(jnp.float32)
                       * w.astype(jnp.float32))

    args = (gate_in, gate_out, x, kernel)
    fwd_ms = _ms_a_call(jax.jit(gates), *args)
    both_ms = _ms_a_call(jax.jit(jax.grad(loss, argnums=range(4))), *args)
    tensor, hbm = b * s * d * 2, flops.hbm_bytes_per_sec()
    report("lfm2", tiny_losses=losses, gates_shape=[b, s, d], taps=taps,
           dtype="bfloat16", fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
           fwd_roofline_pct=100 * 4 * tensor / hbm * 1e3 / fwd_ms,
           fwd_bwd_roofline_pct=100 * 11 * tensor / hbm * 1e3 / both_ms)


def ring_phase(n: int) -> None:
    """Ring attention's Pallas variant over all ``n`` chips against
    softmax_attention on the whole sequence: the flash kernels with traced
    offsets, forward and gradients."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import collectives
    from horovod_tpu.ops.flash_attention import softmax_attention
    from horovod_tpu.parallel.ring_attention import ring_attention

    b, s, h, d = 1, GPT_SEQ * n, 12, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                  for kk in keys)

    @hvd.spmd(in_specs=(P(None, hvd.AXIS),) * 4, out_specs=P())
    def ring_loss(q, k, v, w):
        out = ring_attention(q, k, v, causal=True, impl="pallas")
        return collectives.allreduce(
            jnp.sum((out * w).astype(jnp.float32)), op=hvd.Sum)

    def ref_loss(q, k, v, w):
        return jnp.sum((softmax_attention(q, k, v, causal=True)
                        * w).astype(jnp.float32))

    got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v, w)
    ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v, w)
    errs = {}
    for label, a, b_ in zip(("dq", "dk", "dv"), got, ref):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        check(np.isfinite(a).all(), f"ring {label} is not finite")
        errs[label] = float(np.abs(a - b_).max() / np.abs(b_).max())
        check(errs[label] <= FLASH_TOL,
              f"ring {label} differs from softmax_attention by "
              f"{errs[label]:.3g} of its largest element (> {FLASH_TOL})")
    report("ring_vs_reference", shape=[b, s, h, d], chips=n,
           tolerance=FLASH_TOL, rel_max_err=errs)


def gpt_phase(n: int) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.gpt import gpt2_small, next_token_loss
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    model = gpt2_small(dtype=jnp.bfloat16)
    opt = optax.adam(1e-4)
    sample = jnp.zeros((2, GPT_SEQ), jnp.int32)
    rows = GPT_SEQS_PER_CHIP * n
    ids_host = np.random.default_rng(0).integers(
        0, model.vocab_size, size=(rows, GPT_SEQ)).astype(np.int32)

    def build():
        step = make_train_step(
            apply_fn=lambda v, x, train=True: model.apply(v, x),
            loss_fn=next_token_loss, optimizer=opt)
        return step, init_train_state(model, opt, sample), \
            shard_batch(ids_host)

    def run_step(step, state, ids):
        t0 = time.perf_counter()
        state, loss = step(state, ids, ids)
        loss = float(np.asarray(jax.device_get(loss)))
        return state, loss, time.perf_counter() - t0

    t0 = time.perf_counter()
    step, state, ids = build()
    init_s = time.perf_counter() - t0

    if n > 1:
        shards = ids.addressable_shards
        check(len(shards) == n and len({s.device for s in shards}) == n,
              f"batch has {len(shards)} shards on "
              f"{len({s.device for s in shards})} devices, want {n}")
        check(all(s.data.shape == (rows // n, GPT_SEQ) for s in shards),
              f"batch shards are {[s.data.shape for s in shards]}, want "
              f"{rows // n} rows each")
        for leaf in jax.tree_util.tree_leaves(state):
            check(leaf.sharding.is_fully_replicated
                  and len(leaf.addressable_shards) == n,
                  f"state leaf {leaf.shape} is not replicated on {n} chips")

    # first call = compile + one step; the next two are steady k=1 steps
    losses, secs = [], []
    for _ in range(3):
        state, loss, dt = run_step(step, state, ids)
        losses.append(loss)
        secs.append(dt)
    check(all(np.isfinite(losses)), f"GPT loss not finite: {losses}")
    check(losses[2] < losses[0], f"GPT loss did not fall: {losses}")

    # the module the chip ran: kernels compiled by Mosaic, not interpreted
    t0 = time.perf_counter()
    compiled = jax.jit(step).lower(state, ids, ids).compile()
    hlo_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    mosaic_calls = hlo.count("tpu_custom_call")
    check(mosaic_calls >= 3,
          f"GPT step holds {mosaic_calls} Mosaic custom calls, want the "
          "flash forward, dq and dkv kernels (>= 3)")
    if n > 1:
        check("all-reduce" in hlo, "GPT step holds no all-reduce")

    one_device_loss = None
    if n > 1:
        # the same global batch on a one-device world, same process; the
        # n-chip state goes first, chip 0 needs the room for n times the rows
        del state, ids, step, compiled
        hvd.shutdown()
        hvd.init(platform="tpu", comm=[0])
        step1, state1, ids1 = build()
        _, one_device_loss, _ = run_step(step1, state1, ids1)
        hvd.shutdown()
        hvd.init(platform="tpu")
        check(abs(one_device_loss - losses[0]) <= LOSS_TOL,
              f"first-step loss on {n} chips {losses[0]} differs from the "
              f"one-device world's {one_device_loss} by more than {LOSS_TOL}")

    report("gpt2_small", global_batch=[rows, GPT_SEQ], optimizer="adam",
           init_seconds=round(init_s, 2),
           compile_plus_first_step_seconds=round(secs[0], 2),
           smoke_step_ms=[round(s * 1e3, 1) for s in secs[1:]],
           module_compile_seconds=round(hlo_s, 2),
           mosaic_custom_calls=mosaic_calls, losses=losses,
           one_device_first_loss=one_device_loss,
           module_bytes_per_chip={
               "arguments": mem.argument_size_in_bytes,
               "temporaries": mem.temp_size_in_bytes,
               "outputs": mem.output_size_in_bytes},
           peak_bytes_in_use=peak_bytes())


def resnet_phase() -> None:
    from examples.synthetic_benchmark import parse_args, run

    result = run(parse_args([
        "--platform", "tpu",
        "--batch-size", str(RESNET_BATCH_PER_CHIP),
        "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1",
        "--num-iters", "2",
    ]))
    losses = result["losses"]
    check(len(losses) == 3, f"ResNet-50 took {len(losses)} steps, want 3")
    check(all(np.isfinite(losses)), f"ResNet-50 loss not finite: {losses}")
    check(losses[2] < losses[0], f"ResNet-50 loss did not fall: {losses}")
    report("resnet50", batch_per_chip=RESNET_BATCH_PER_CHIP,
           optimizer="sgd momentum 0.9",
           compile_plus_first_step_seconds=round(result["warmup_sec"], 2),
           smoke_step_ms=round(RESNET_BATCH_PER_CHIP * 1e3
                               / result["img_sec_per_chip"], 1),
           losses=losses, peak_bytes_in_use=peak_bytes())


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    report("device", **device,
           versions={p: metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")})
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2

    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.utils import flops

    n = device["count"]
    # platform="tpu": a machine without a chip raises here instead of
    # falling back to the CPU with a warning
    hvd.init(platform="tpu")
    check(hvd.size() == n, f"world is {hvd.size()} ranks on {n} chips")
    cache_dir = core.compile_cache_dir()
    entries_before = cache_entries(cache_dir)
    report("setup", compile_cache_dir=cache_dir,
           compile_cache_entries=len(entries_before),
           peak_flops=flops.require_peak_flops())

    only = [globals()[name] for name in sys.argv[1:]]
    if only:
        # the named phases alone (none of them takes an argument it lacks a
        # default for): ``python chip_smoke.py ssd_phase``
        for phase in only:
            phase()
        hvd.shutdown()
        print(json.dumps({"ok": True, "device": device,
                          "phases": sys.argv[1:]}), flush=True)
        return 0
    flash_phase()
    # Qwen3-Next's attention layer: 16 heads of 256 (the kernels were swept
    # at 64 only), and its DeltaNet layers' scan
    flash_phase(1, 2048, 16, 256)
    # SDAR's attention: 32 heads of 128 over a doubled sequence under the
    # block-diffusion mask
    block_diffusion_phase()
    # Kanana-2's latent attention: q.k at 192 (128 + 64 rotary), v at 128
    latent_attention_phase()
    # Mellum-2's window layers: 32 heads of 128, a row sees 1024 keys
    sliding_window_phase()
    scan_phase()
    # Nemotron-3-Nano's Mamba-2 blocks: 64 heads of 64 over a state of 128
    ssd_phase()
    # LFM2's toy through the step builder, and its gates and taps alone
    lfm2_phase()
    if n > 1:
        ring_phase(n)
    gpt_phase(n)
    resnet_phase()

    entries_after = cache_entries(cache_dir)
    report("compile_cache", dir=cache_dir,
           entries_before=len(entries_before),
           entries_after=len(entries_after),
           new_entries=sorted(entries_after - entries_before))
    hvd.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
