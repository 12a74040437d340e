"""The flattened grid of the causal and block-diffusion flash launchers:
where the offsets are Python ints the streamed axis of the grid is the list
of live (resident block, streamed block) pairs, read from a scalar-
prefetched table (``ops/flash_attention._pair_table``).  Forward and the
three gradients give the bits the rectangle gives, the table names every
live pair once and a resident block's pairs one after the other, traced
offsets and a sliding window keep the launchers and the Mosaic bodies they
had, ``grid_census`` launches the live steps and no other at the shapes of
the benchmark's cells, and the counter counts them."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa

from test_flash_block_diffusion import dense_mask as bd_dense_mask


@pytest.fixture(autouse=True)
def _on_cpu():
    """Exact f32 on the CPU whatever backends are present (as in
    test_flash_attention.py)."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def _forget_the_traces():
    """The launchers are jitted with everything but the arrays static, and
    ``MAX_PAIRS`` is read while they trace."""
    for launcher in (fa._fwd_call, fa._dq_call, fa._dkv_call):
        launcher.clear_cache()
    fa._flash_fn.cache_clear()


@pytest.fixture
def rectangle(monkeypatch):
    """``with rectangle():`` the launchers keep the rectangle, as for a
    table that scalar memory does not hold."""
    import contextlib

    @contextlib.contextmanager
    def no_table(most=0):
        _forget_the_traces()
        with monkeypatch.context() as patch:
            patch.setattr(fa, "MAX_PAIRS", most)
            yield
        _forget_the_traces()

    _forget_the_traces()
    yield no_table
    _forget_the_traces()


def _bd(block, noised):
    return fa.block_diffusion_mask(block, noised)


# name: (mask, [b, sq, h, d], v's head size, sk, block_q, block_k,
# (q_offset, kv_offset)).  The cells' calls scaled down by 16 (1024 x 512
# tiles at 16 384 rows are 64 x 32 at 1024), then what the table has to
# get right besides: block counts that are odd, rows a block under and over
# the keys', offsets other than zero (aligned to the blocks and not; a
# block of rows before every key; keys the last rows do not reach), lengths
# that differ, a diffusion block wider than a tile.
GRID_CASES = {
    "gpt2s_16k": (fa.CAUSAL, (1, 1024, 1, 16), 16, 1024, 64, 32, (0, 0)),
    "sdar_bd4_8k": (_bd(4, 512), (1, 1024, 1, 16), 16, 1024, 64, 32, (0, 0)),
    "kanana2_8k": (fa.CAUSAL, (1, 512, 1, 24), 16, 512, 64, 32, (0, 0)),
    "gpt2s_1k": (fa.CAUSAL, (2, 64, 2, 16), 16, 64, 64, 32, (0, 0)),
    "five_blocks": (fa.CAUSAL, (1, 640, 1, 16), 16, 640, 128, 64, (0, 0)),
    "three_blocks_bd": (_bd(16, 192), (1, 384, 1, 16), 16, 384, 64, 64,
                        (0, 0)),
    "rows_under_keys": (fa.CAUSAL, (1, 512, 1, 16), 16, 512, 32, 128,
                        (0, 0)),
    "rows_under_keys_bd": (_bd(8, 256), (1, 512, 1, 16), 16, 512, 32, 64,
                           (0, 0)),
    "queries_ahead": (fa.CAUSAL, (1, 256, 1, 16), 16, 256, 64, 32,
                      (128, 0)),
    "keys_ahead": (fa.CAUSAL, (1, 256, 1, 16), 16, 256, 64, 32, (0, 128)),
    "off_the_blocks": (fa.CAUSAL, (1, 256, 1, 16), 16, 256, 64, 32,
                       (100, 37)),
    "short_queries": (fa.CAUSAL, (1, 128, 1, 16), 16, 512, 32, 32,
                      (200, 0)),
    "short_keys": (fa.CAUSAL, (1, 512, 1, 16), 16, 128, 64, 32, (0, 300)),
    "bd_block_over_the_tile": (_bd(64, 256), (1, 512, 1, 16), 16, 512, 32,
                               32, (0, 0)),
}


def _out_and_grads(rng, case):
    mask, (b, sq, h, d), dv, sk, bq, bk, (q_off, kv_off) = GRID_CASES[case]
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape).astype(np.float32))
    q, k, v, w = (mk(b, sq, h, d), mk(b, sk, h, d), mk(b, sk, h, dv),
                  mk(b, sq, h, dv))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=bq, block_k=bk,
                                  q_offset=q_off, kv_offset=kv_off,
                                  interpret=True)

    # one program: the forward's output beside the three gradients
    (_, out), grads = jax.jit(jax.value_and_grad(
        lambda q, k, v: (lambda o: (jnp.sum(o * w), o))(flash(q, k, v)),
        argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return [np.asarray(x) for x in (out, *grads)]


@pytest.mark.parametrize("case", sorted(GRID_CASES))
def test_the_flattened_grid_gives_the_rectangles_bits(case, rectangle):
    """Forward, dq, dk and dv: the same arithmetic on the same tiles in
    the same order, so the same bits."""
    mask, (_, sq, _, _), _, sk, bq, bk, offs = GRID_CASES[case]
    flat = _out_and_grads(np.random.default_rng(43), case)
    steps = fa.grid_census(sq, sk, bq, bk, mask, *offs)
    with rectangle():
        whole = _out_and_grads(np.random.default_rng(43), case)
        every = fa.grid_census(sq, sk, bq, bk, mask, *offs)
    for name, a, b in zip(("o", "dq", "dk", "dv"), flat, whole):
        assert np.isfinite(a).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)
    for kernel in ("fwd", "dq", "dkv"):
        assert steps[kernel]["live"] == every[kernel]["live"]
        assert steps[kernel]["launched"] <= every[kernel]["launched"]


def _dense(case):
    """Which pairs the case's mask allows, ``[sq, sk]``."""
    mask, (_, sq, _, _), _, sk, _, _, (q_off, kv_off) = GRID_CASES[case]
    if mask.kind == "block_diffusion":
        return bd_dense_mask(mask.block, mask.noised)
    return ((q_off + np.arange(sq))[:, None]
            >= (kv_off + np.arange(sk))[None, :])


@pytest.mark.parametrize("side", ["kv", "q"])
@pytest.mark.parametrize("case", sorted(GRID_CASES))
def test_the_table_names_every_live_pair_once_a_resident_block_at_a_time(
        case, side):
    """A resident block's pairs are consecutive and in its order (its
    outputs are written back once, at the ``last`` pair; the index maps
    return one block through them), ``first`` and ``last`` mark their ends,
    and the pairs are the blocks somebody sees something in, by brute force
    over the mask, plus one step for a resident block nobody sees anything
    from (its outputs' zeros)."""
    mask, (_, sq, _, _), _, sk, bq, bk, offs = GRID_CASES[case]
    seen = _dense(case)
    if side == "kv":
        rows, _, keys, steps, _ = fa._kv_grid(sq, sk, bq, bk, mask, offs)
        table = fa._pair_table(fa._kv_blocks_seen, steps, mask, sq, sk, rows,
                               keys, offs)
    else:
        _, rows, keys, steps = fa._q_grid(sq, sk, bq, bk, mask, offs)
        table = fa._pair_table(fa._q_blocks_seen, steps, mask, sq, sk, rows,
                               keys, offs)
        seen, rows, keys = seen.T, keys, rows
    live = seen.reshape(seen.shape[0] // rows, rows,
                        seen.shape[1] // keys, keys).any(axis=(1, 3))
    census = fa.grid_census(sq, sk, bq, bk, mask, *offs)[
        "fwd" if side == "kv" else "dkv"]
    if table is None:
        # a rectangle without an idle step is kept
        assert live.all() or live.shape[1] == 1
        assert census == {"launched": live.size, "live": int(live.sum())}
        return
    resident, streamed, edge = table.reshape(3, -1).tolist()
    assert resident == sorted(resident)
    assert sorted(set(resident)) == list(range(live.shape[0]))
    want = []
    for block, row in enumerate(live):
        blocks = np.flatnonzero(row).tolist() or [0]
        want += [(block, s, (n == 0) + 2 * (n == len(blocks) - 1))
                 for n, s in enumerate(blocks)]
    assert list(zip(resident, streamed, edge)) == want
    assert len(want) < live.size
    assert census == {"launched": len(want), "live": int(live.sum())}


#: sha256 of the three Mosaic bodies (forward, dq, dkv; printed without
#: locations, lowered for a TPU from here) of a call's gradient at
#: ``[1, 2048, 2, 128]`` bfloat16 as the parent of PR 43 lowers them: the
#: calls whose launchers that PR leaves alone (the causal body does not
#: say whether its offsets were Python ints).
BODIES_BEFORE_THE_TABLE = {
    "causal_traced_offsets":
        "7f67a2a77fbd1c07865be85c5c84bf1c4cbdf9246e712a91f20243e7c5d89c17",
    "window":
        "49a56113de6ced921d28f18862759d9529c5b5357cd4b87dd2ddf96a02bfd1a9",
    "window_traced_offsets":
        "18060f8deddb50152947a4e0fb800f5e9742be9f4f291dc8eaad9a9109b7cd21",
    "no_mask":
        "20f158387d89354691088c295b747405ad53e9f76f56d546f11cde35496a194f",
    # two blocks of rows over one of keys: no idle step, no table
    "causal_one_block_of_keys":
        "7f67a2a77fbd1c07865be85c5c84bf1c4cbdf9246e712a91f20243e7c5d89c17",
}


def _mosaic_bodies(monkeypatch, mask_kw, traced, s=2048, heads=2,
                   kv_heads=None):
    """The Mosaic bodies of a call's gradient at ``s`` rows, lowered for a
    TPU; k and v at ``kv_heads`` heads where q's ``heads`` share them."""
    from jax._src import tpu_custom_call

    bodies = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        bodies.append(module.operation.get_asm(enable_debug_info=False))
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    x = jax.ShapeDtypeStruct((1, s, heads, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, kv_heads or heads, 128), jnp.bfloat16)
    at = (jax.ShapeDtypeStruct((), jnp.int32),) if traced else ()

    def loss(q, k, v, *at):
        return fa.flash_attention(
            q, k, v, interpret=False, **mask_kw,
            **(dict(q_offset=at[0], kv_offset=at[0]) if at else {})).astype(
                jnp.float32).sum()

    jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, kv, kv, *at).lower(
        lowering_platforms=("tpu",))
    return bodies


@pytest.mark.parametrize("call", sorted(BODIES_BEFORE_THE_TABLE))
def test_traced_offsets_and_a_window_keep_the_bodies_they_had(
        call, monkeypatch):
    mask_kw = (dict(mask=fa.sliding_window_mask(256)) if "window" in call
               else dict(causal=call.startswith("causal")))
    bodies = _mosaic_bodies(monkeypatch, mask_kw, "traced" in call)
    assert len(bodies) == 3
    # a rectangle: two parallel axes of batch and heads, the resident
    # blocks', the streamed blocks'
    assert all("#tpu.dimension_semantics<arbitrary>" in body
               and body.count("#tpu.dimension_semantics<parallel>") == 3
               for body in bodies)
    assert hashlib.sha256("\n".join(bodies).encode()).hexdigest() \
        == BODIES_BEFORE_THE_TABLE[call]


@pytest.mark.parametrize("mask", [fa.CAUSAL, _bd(4, 4096)],
                         ids=lambda m: m.kind)
def test_static_offsets_take_the_table_and_one_streamed_axis(
        mask, monkeypatch):
    """The flattened grid is ``(batch, heads, pairs)``, and the scalar
    operand holds the two offsets and three words a pair."""
    bodies = _mosaic_bodies(monkeypatch, dict(mask=mask), False, s=8192)
    steps = fa.grid_census(8192, 8192, *fa.default_blocks(128, mask), mask)
    assert len(bodies) == 3
    for kernel, body in zip(("fwd", "dq", "dkv"), bodies):
        assert body.count("#tpu.dimension_semantics<parallel>") == 2
        pairs = steps[kernel]["launched"]
        assert f"iteration_bounds = array<i64: 1, 2, {pairs}>" in body
        assert f"memref<{2 + 3 * pairs}xi32, #tpu.memory_space<smem>>" in body


# The static causal and block-diffusion calls of the benchmark's cells:
# (rows, q's head size, mask): live steps a head of forward and dq, of dkv,
# at ``default_blocks``.  ``gpt2s-1k-dp4`` calls as ``gpt2s-1k`` does;
# ``mellum2-16k``'s window layers keep the fitted grid
# (``test_flash_window.py``).
CELL_CALLS = {
    "gpt2s-1k": ((1024, 64, fa.CAUSAL), 2, 2),
    "gpt2s-4k": ((4096, 64, fa.CAUSAL), 6, 8),
    "gpt2s-16k": ((16384, 64, fa.CAUSAL), 72, 80),
    "qwen3next-8k": ((8192, 256, fa.CAUSAL), 40, 40),
    "sdar-bd4-8k": ((16384, 128, _bd(4, 8192)), 48, 64),
    "kanana2-8k": ((8192, 192, fa.CAUSAL), 20, 24),
    "mellum2-16k full layer": ((16384, 128, fa.CAUSAL), 72, 80),
    "nemotron3-8k": ((8192, 128, fa.CAUSAL), 20, 24),
}


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_the_cells_calls_launch_their_live_steps_and_no_other(cell,
                                                               rectangle):
    (s, d, mask), kv_side, q_side = CELL_CALLS[cell]
    blocks = fa.default_blocks(d, mask)
    steps = fa.grid_census(s, s, *blocks, mask)
    assert steps == {"fwd": {"launched": kv_side, "live": kv_side},
                     "dq": {"launched": kv_side, "live": kv_side},
                     "dkv": {"launched": q_side, "live": q_side}}
    with rectangle():
        every = fa.grid_census(s, s, *blocks, mask)
    assert all(every[k]["live"] == steps[k]["live"]
               and every[k]["launched"] >= steps[k]["launched"]
               for k in steps)


def test_the_rectangle_of_the_long_cells_was_two_fifths_to_three_fifths_idle(
        rectangle):
    """What ISSUE 43 counted: 128 steps a head where 48 / 64 (block
    diffusion) and 72 / 80 (causal, 16 384 rows) are live."""
    with rectangle():
        for cell in ("sdar-bd4-8k", "gpt2s-16k"):
            (s, d, mask), kv_side, q_side = CELL_CALLS[cell]
            every = fa.grid_census(s, s, *fa.default_blocks(d, mask), mask)
            assert every["fwd"] == {"launched": 128, "live": kv_side}
            assert every["dkv"] == {"launched": 128, "live": q_side}


def test_a_table_past_scalar_memory_keeps_the_rectangle(rectangle):
    """Decided from shapes: a call of more pairs than ``MAX_PAIRS`` runs
    the rectangle, and gives the same bits."""
    mask, (_, sq, _, _), _, sk, bq, bk, offs = GRID_CASES["sdar_bd4_8k"]
    flat = _out_and_grads(np.random.default_rng(7), "sdar_bd4_8k")
    steps = fa.grid_census(sq, sk, bq, bk, mask, *offs)
    assert steps["fwd"]["launched"] == steps["fwd"]["live"] == 48
    with rectangle(most=47):
        assert fa._pair_table(fa._kv_blocks_seen, 8, mask, sq, sk, bq,
                              4 * bk, offs) is None
        # dkv's 64 pairs are past the bound too
        assert fa.grid_census(sq, sk, bq, bk, mask, *offs) == {
            "fwd": {"launched": 128, "live": 48},
            "dq": {"launched": 128, "live": 48},
            "dkv": {"launched": 128, "live": 64}}
        whole = _out_and_grads(np.random.default_rng(7), "sdar_bd4_8k")
    for a, b in zip(flat, whole):
        np.testing.assert_array_equal(a, b)
    # the bound holds the cells' calls many times over and stays inside a
    # v5e's 1 MiB of scalar memory
    assert 80 * 16 < fa.MAX_PAIRS and (2 + 3 * fa.MAX_PAIRS) * 4 < 2 ** 19


def test_rows_that_see_no_key_come_out_zero(rectangle):
    """A resident block nobody sees anything from keeps one step: its
    outputs are written (zeros), not left as they were found."""
    q = jnp.ones((1, 128, 1, 16), jnp.float32)

    def grads(kv_offset):
        return jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, q_offset=0,
            kv_offset=kv_offset, interpret=True).sum(),
            argnums=(0, 1, 2))(q, q, q)

    # the first 64 rows lie before every key; the keys past row 127 (all
    # but the first 64) are seen by nobody
    dq, dk, dv = grads(64)
    assert not np.asarray(dq[:, :64]).any()
    assert np.asarray(dv[:, :64]).all()
    assert not np.asarray(dk[:, 64:]).any() and not np.asarray(
        dv[:, 64:]).any()
    # four blocks of rows over one block of keys (four tiles a step)
    assert fa.grid_census(128, 128, 32, 32, True, 0, 64)["fwd"] == {
        "launched": 4, "live": 2}
    # nobody sees anything at all
    assert not any(np.asarray(g).any() for g in grads(128))


def _counted(label):
    from horovod_tpu import metrics

    return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_flash_grid_steps_traced_total", {}).get("samples", [])
            if s["labels"]["mask"] == label}


def _newly_counted(label, trace):
    before = _counted(label)
    trace()
    return {k: v - before.get(k, 0) for k, v in _counted(label).items()
            if v - before.get(k, 0)}


@pytest.mark.parametrize("cell", ["sdar-bd4-8k", "gpt2s-16k", "kanana2-8k"])
def test_the_counter_reads_no_idle_step_in_a_cells_call(cell, monkeypatch):
    """Traced and not run, at the cell's own shape: ``launched`` and
    ``live`` are the census's numbers a head times the heads, ``idle`` is
    counted (the label exists) and reads 0."""
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    (s, d, mask), kv_side, q_side = CELL_CALLS[cell]
    heads = 3
    x = jax.ShapeDtypeStruct((1, s, heads, d), jnp.bfloat16)
    got = _newly_counted(mask.label, lambda: jax.eval_shape(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=mask, interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), x, x, x))
    assert got == {(kernel, kind): heads * n
                   for kernel, n in (("fwd", kv_side), ("dq", kv_side),
                                     ("dkv", q_side))
                   for kind in ("launched", "live")}
    assert {k for k in _counted(mask.label) if k[1] == "idle"} == {
        ("fwd", "idle"), ("dq", "idle"), ("dkv", "idle")}


def test_the_counter_counts_idle_steps_where_there_are_some(monkeypatch,
                                                            rectangle):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    x = jax.ShapeDtypeStruct((2, 3, 512, 8), jnp.float32)

    def trace(q_offset, kv_offset):
        return lambda: jax.eval_shape(lambda q, *at: fa.mha_partial(
            q, q, q, *(at or (q_offset, kv_offset)), causal=True, scale=1.0,
            block_q=32, block_k=32, interpret=True), x, *(
                [jax.ShapeDtypeStruct((), jnp.int32)] * 2
                if q_offset is None else []))

    # six heads of sixteen blocks of rows over four blocks of keys (four
    # tiles a step).  The first eight lie before every key and keep a step
    # each; the others see one block of keys, then two
    assert _newly_counted("causal", trace(0, 256)) == {
        ("fwd", "launched"): 6 * 20, ("fwd", "live"): 6 * 12,
        ("fwd", "idle"): 6 * 8}
    # traced offsets: the rectangle, and what is live is data
    assert _newly_counted("causal", trace(None, None)) == {
        ("fwd", "launched"): 6 * 64}
    with rectangle():
        assert _newly_counted("causal", trace(0, 0)) == {
            ("fwd", "launched"): 6 * 64, ("fwd", "live"): 6 * 40,
            ("fwd", "idle"): 6 * 24}
