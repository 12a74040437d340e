"""The flash kernels under the sliding-window mask: forward and all three
gradients in interpreter mode against materialised masked attention,
``tile_census`` against a brute-force count over the mask and a count by
hand, dkv's range of query tiles, the fitted grid (every live block named by
one step, no other step live), the traced tile and grid-step counters.  Row ``i`` sees keys ``j`` with ``i - window < j <= i``: itself
and the ``window - 1`` keys before it, by global position."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa


@pytest.fixture(autouse=True)
def _on_cpu():
    """Exact f32 on the CPU whatever backends are present (as in
    test_flash_attention.py)."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def dense_mask(window: int, sq: int, sk: int, q_offset: int = 0,
               kv_offset: int = 0) -> np.ndarray:
    """The definition, pair by pair: ``[sq, sk]`` booleans."""
    i = q_offset + np.arange(sq)[:, None]
    j = kv_offset + np.arange(sk)[None, :]
    return (j <= i) & (i - j < window)


def test_the_mask_is_hugging_faces_sliding_window():
    """``kv_idx <= q_idx`` and ``kv_idx > q_idx - sliding_window``; over
    ``s`` rows it allows ``s w - w (w - 1) / 2`` pairs (the first ``w`` rows
    see fewer)."""
    for window, s in ((1, 8), (4, 64), (16, 64), (64, 64), (100, 64)):
        i, j = np.arange(s)[:, None], np.arange(s)[None, :]
        assert (dense_mask(window, s, s) == ((j <= i) & (j > i - window))
                ).all()
        w = min(window, s)
        assert dense_mask(window, s, s).sum() == s * w - w * (w - 1) // 2
    # the benchmark's cell: 992.0 allowed pairs a row, 16 253 440 a head
    assert 16384 * 1024 - 1024 * 1023 // 2 == 16_253_440


def test_a_window_is_at_least_one_key():
    with pytest.raises(ValueError, match="sees nothing"):
        fa.sliding_window_mask(0)
    assert fa.sliding_window_mask(1024).label == "sliding_window_w1024"
    assert fa.CAUSAL.label == "causal" and fa.NO_MASK.label == "none"
    assert fa.block_diffusion_mask(4, 64).label == "block_diffusion_b4"


def _dense(q, k, v, seen):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (window, rows, tile rows, tile keys, q.k head size, v head size): windows
# smaller than a tile, equal to one, larger than one, off the tiles' edges,
# as long as the sequence and longer (the causal mask), one key (a row sees
# itself alone), tiles wider than tall and taller than wide, and v's head
# size apart from q.k's
WINDOW_CASES = {
    "w4_under_the_tile": (4, 64, 16, 16, 16, 16),
    "w16_one_tile": (16, 64, 16, 16, 16, 16),
    "w16_tall_tiles": (16, 128, 32, 16, 16, 16),
    "w16_wide_tiles": (16, 128, 16, 32, 16, 16),
    "w24_off_the_tiles": (24, 96, 16, 16, 16, 16),
    "w40_over_the_tile": (40, 128, 16, 16, 16, 16),
    "w64_the_sequence": (64, 64, 16, 16, 16, 16),
    "w100_past_the_sequence": (100, 64, 16, 16, 16, 16),
    "w1_itself_alone": (1, 32, 8, 8, 16, 16),
    "w16_v_narrower": (16, 64, 16, 16, 24, 16),
    "w24_v_wider": (24, 96, 32, 16, 16, 32),
}


def _out_and_grads(f, q, k, v, w):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: (jnp.sum(f(q, k, v) * w), f(q, k, v)),
        argnums=(0, 1, 2), has_aux=True))(q, k, v)


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_flash_window_matches_dense(rng, case):
    window, s, bq, bk, d, dv = WINDOW_CASES[case]
    mask = fa.sliding_window_mask(window)
    seen = jnp.asarray(dense_mask(window, s, s))
    mk = lambda width: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, s, 2, width)).astype(np.float32))
    q, k, v, w = mk(d), mk(d), mk(dv), mk(dv)
    (_, out), grads = _out_and_grads(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=mask, block_q=bq, block_k=bk, interpret=True),
        q, k, v, w)
    (_, want), want_grads = _out_and_grads(
        lambda q, k, v: _dense(q, k, v, seen), q, k, v, w)
    assert out.shape == (1, s, 2, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), grads, want_grads):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


# (window, q rows, keys, q_offset, kv_offset): a later shard of the queries
# against an earlier shard of the keys, as ring attention's hops pass them
OFFSET_CASES = {
    "the_diagonal_shard": (24, 64, 64, 64, 64),
    "one_shard_back": (24, 64, 64, 64, 0),
    "half_in_the_window": (40, 32, 64, 80, 16),
    "out_of_the_window": (16, 32, 32, 96, 0),
    "keys_ahead_of_the_rows": (16, 32, 32, 0, 64),
}


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("case", sorted(OFFSET_CASES))
def test_the_window_moves_with_the_offsets(rng, case, traced):
    """The partial triple of one q shard against one kv shard, with the
    offsets static and traced, and both backward kernels, against the dense
    mask at those positions; a row that sees no key of the shard comes back
    with ``l`` 0."""
    window, sq, sk, q_off, kv_off = OFFSET_CASES[case]
    mask = fa.sliding_window_mask(window)
    seen = dense_mask(window, sq, sk, q_off, kv_off)
    mk = lambda s: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, 2, s, 16)).astype(np.float32))
    q, k, v, do = mk(sq), mk(sk), mk(sk), mk(sq)
    kw = dict(causal=mask, scale=0.25, block_q=16, block_k=16,
              interpret=True)

    def partial(q_off, kv_off):
        return fa.mha_partial(q, k, v, q_off, kv_off, **kw)

    o, m, l = (jax.jit(partial)(jnp.int32(q_off), jnp.int32(kv_off))
               if traced else partial(q_off, kv_off))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
    s = jnp.where(seen[None, None], s, -jnp.inf)
    some = seen.any(axis=1)
    want_m = jnp.where(some[None, None, :, None],
                       jnp.max(s, axis=-1, keepdims=True), fa.M_INIT)
    p = jnp.where(seen[None, None], jnp.exp(s - want_m), 0.0)
    np.testing.assert_allclose(np.asarray(l[..., 0]),
                               np.asarray(p.sum(-1)), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(jnp.einsum("bhqk,bhkd->bhqd", p, v)),
        atol=2e-5, rtol=2e-5)
    assert (np.asarray(l[0, 0, :, 0]) == 0).tolist() == (~some).tolist()
    # the backward kernels at the same offsets, from any row statistics
    lse = jnp.where(some[None, None, :, None],
                    want_m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
    delta = jnp.asarray(rng.normal(size=(1, 2, sq, 1)).astype(np.float32))
    pn = jnp.where(seen[None, None], jnp.exp(s - lse), 0.0)
    ds = pn * (jnp.einsum("bhqd,bhkd->bhqk", do, v) - delta)
    offs = (jnp.int32(q_off), jnp.int32(kv_off)) if traced \
        else (q_off, kv_off)
    dq = fa.mha_bwd_dq(q, k, v, do, lse, delta, *offs, **kw)
    dk, dv = fa.mha_bwd_dkv(q, k, v, do, lse, delta, *offs, **kw)
    for name, got, want in (
            ("dq", dq, 0.25 * jnp.einsum("bhqk,bhkd->bhqd", ds, k)),
            ("dk", dk, 0.25 * jnp.einsum("bhqk,bhqd->bhkd", ds, q)),
            ("dv", dv, jnp.einsum("bhqk,bhqd->bhkd", pn, do))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5, err_msg=name)


CENSUS_CASES = [
    # window, rows, block_q, block_k, q_offset, kv_offset
    (1024, 16384, 1024, 512, 0, 0),
    (1024, 16384, 512, 512, 0, 0),
    (4096, 16384, 1024, 512, 0, 0),
    (4, 64, 16, 16, 0, 0),
    (16, 64, 16, 16, 0, 0),
    (17, 64, 16, 16, 0, 0),
    (24, 96, 32, 16, 0, 0),
    (24, 96, 16, 32, 0, 0),
    (40, 128, 16, 16, 0, 0),
    (64, 64, 16, 16, 0, 0),
    (100, 64, 16, 16, 0, 0),
    (1, 32, 8, 8, 0, 0),
    (24, 64, 16, 16, 64, 0),
    (40, 64, 16, 16, 80, 16),
    (16, 32, 16, 16, 0, 64),
]


@pytest.mark.parametrize("window,s,bq,bk,q_off,kv_off", CENSUS_CASES)
def test_tile_census_matches_the_window(window, s, bq, bk, q_off, kv_off):
    mask = fa.sliding_window_mask(window)
    fq, fk = fa._check_blocks(s, s, bq, bk, mask)    # as the kernels fit
    tiles = dense_mask(window, s, s, q_off, kv_off).reshape(
        s // fq, fq, s // fk, fk)
    every, some = tiles.all(axis=(1, 3)), tiles.any(axis=(1, 3))
    assert fa.tile_census(s, s, bq, bk, mask, q_off, kv_off) == {
        "skipped": int((~some).sum()), "full": int(every.sum()),
        "crossed": int((some & ~every).sum())}


# by hand, 16 384 rows under a window of 1024.  At the causal tiles (1024 rows
# x 512 keys) a query block's rows ``[q, q + 1024)`` see keys ``(q - 1024, q
# + 1024)``, four tiles (two for the first block, which has nothing behind
# it), and none of them whole: the window's low edge crosses the two behind
# the block, the diagonal the two inside it: 62 of 512 tiles a head, half of
# whose pairs are masked.  At the tiles the window chooses (512 x 512) a
# block sees three tiles, the one in the middle whole (one for the first
# block, two for the second): 93 of 1024 for the same allowed pairs, a
# third of whose pairs are masked.
CELL_CENSUS = {
    (1024, 512): {"skipped": 512 - 62, "full": 0, "crossed": 2 + 15 * 4},
    (512, 512): {"skipped": 1024 - 93, "full": 0 + 1 + 30,
                 "crossed": 1 + 1 + 30 * 2},
}


@pytest.mark.parametrize("blocks", sorted(CELL_CENSUS))
def test_tile_census_of_the_window_cell_by_hand(blocks):
    mask = fa.sliding_window_mask(1024)
    assert fa.default_blocks(128) == (1024, 512)
    assert fa.default_blocks(128, mask) == (512, 512)
    assert fa.tile_census(16384, 16384, *blocks, mask) == CELL_CENSUS[blocks]
    # the allowed pairs are the same ones: whole tiles and crossed halves
    assert fa.tile_census(16384, 16384, 1024, 512, True) == {
        "skipped": 240, "full": 240, "crossed": 32}
    # a window of four tiles of keys: whole tiles between the two edges
    assert fa.tile_census(16384, 16384, 1024, 512,
                          fa.sliding_window_mask(4096)) == {
        "skipped": 512 - (2 + 4 + 6 + 8 + 12 * 10),
        "full": 0 + 2 + 4 + 6 + 12 * 6, "crossed": 2 + 2 + 2 + 2 + 12 * 4}


def test_the_tiles_a_caller_gets_follow_the_window():
    """Half the window's length in rows, between 256 and the causal tile's
    (itself by head size); a grid step's block no longer than the window."""
    rows = {w: fa.default_blocks(128, fa.sliding_window_mask(w))[0]
            for w in (1, 256, 1023, 1024, 2047, 2048, 4096, 1 << 20)}
    assert rows == {1: 256, 256: 256, 1023: 256, 1024: 512, 2047: 512,
                    2048: 1024, 4096: 1024, 1 << 20: 1024}
    assert fa.default_blocks(256, fa.sliding_window_mask(4096)) == (512, 512)
    assert fa.default_blocks(128, fa.CAUSAL) == fa.default_blocks(128)
    steps = {w: fa._tiles_per_step(16384, 512, fa.sliding_window_mask(w))
             for w in (16, 512, 1024, 1536, 2048, 4096)}
    assert steps == {16: 1, 512: 1, 1024: 2, 1536: 2, 2048: 4, 4096: 4}
    assert fa._tiles_per_step(16384, 512, fa.CAUSAL) == fa.TILES_PER_STEP


@pytest.mark.parametrize("window,s,bq,bk,q_off,kv_off", CENSUS_CASES[3:])
def test_what_dkv_skips_is_what_the_window_hides(window, s, bq, bk, q_off,
                                                 kv_off):
    """dkv's range of query tiles for a block of keys has a lower end (the
    diagonal) and, new with this mask, an upper one (the window)."""
    mask = fa.sliding_window_mask(window)
    fq, fk = fa._check_blocks(s, s, bq, bk, mask)
    some = dense_mask(window, s, s, q_off, kv_off).reshape(
        s // fq, fq, s // fk, fk).any(axis=(1, 3))
    for jk in range(s // fk):
        lo, hi = fa._q_tiles_seen(mask, kv_off + jk * fk, fk, q_off,
                                  s // fq, fq)
        assert list(range(lo, hi)) == list(np.flatnonzero(some[:, jk])), jk
    for i in range(s // fq):
        first, _, live = fa._kv_tiles_seen(mask, q_off + i * fq, fq, kv_off,
                                           s // fk, fk)
        assert list(range(first, first + live)) \
            == list(np.flatnonzero(some[i])), i


# the cell's window call at the tiles it gets (512 x 512; forward and dq
# hold two tiles of rows and stream two tiles of keys a step, dkv holds a
# tile of keys and streams two tiles of rows: blocks of 1024, 16 of them),
# by hand: (resident block, the blocks its steps name, which steps are live)
FITTED_STEPS = {
    "fwd_the_first_block_has_nothing_behind": ("fwd", 0, [0, 0], [1, 0]),
    "fwd_rows_5120_reach_back_to_4097": ("fwd", 5, [4, 5], [1, 1]),
    "fwd_the_last_block": ("fwd", 15, [14, 15], [1, 1]),
    "dkv_keys_0_are_seen_to_row_1534": ("dkv", 0, [0, 1], [1, 1]),
    "dkv_keys_512_are_seen_to_row_2046": ("dkv", 1, [0, 1], [1, 1]),
    "dkv_keys_1024_are_seen_to_row_2558": ("dkv", 2, [1, 2], [1, 1]),
    "dkv_the_last_keys_have_nothing_ahead": ("dkv", 31, [15, 15], [1, 0]),
}


@pytest.mark.parametrize("case", sorted(FITTED_STEPS))
def test_a_step_of_the_fitted_grid_names_a_block_of_the_one_range(case):
    """Step ``j`` names the ``j``-th live block; a step past the last live
    one names that one again (nothing is fetched) and is not live."""
    kernel, resident, blocks, live = FITTED_STEPS[case]
    mask = fa.sliding_window_mask(1024)
    if kernel == "fwd":
        rows, _, keys, steps, chunk = fa._kv_grid(16384, 16384, 512, 512,
                                                  mask, (0, 0))
        assert (rows, keys, steps, chunk) == (1024, 1024, 2, 512)
        named = [fa._window_kv_block(mask, resident * rows, rows, 0,
                                     16384 // keys, keys, j)
                 for j in range(steps)]
    else:
        _, rows, keys, steps = fa._q_grid(16384, 16384, 512, 512, mask,
                                          (0, 0))
        assert (rows, keys, steps) == (1024, 512, 2)
        named = [fa._window_q_block(mask, resident * keys, keys, 0,
                                    16384 // rows, rows, j)
                 for j in range(steps)]
    assert [int(b) for b, _ in named] == blocks
    assert [int(on) for _, on in named] == live


def test_a_range_with_no_live_block_names_a_block_that_exists():
    for first, count, n in ((3, 0, 4), (0, 0, 4), (4, 0, 4), (2, 2, 8)):
        named = [fa._window_block(j, first, count, n) for j in range(4)]
        assert all(0 <= int(b) < n for b, _ in named)
        assert [bool(on) for _, on in named] == [j < count for j in range(4)]
        if count:
            assert [int(b) for b, _ in named] == [2, 3, 3, 3]


def _live_blocks(seen, rows, keys):
    """``[row blocks, key blocks]``: which blocks hold an allowed pair."""
    sq, sk = seen.shape
    return seen.reshape(sq // rows, rows, sk // keys, keys).any(axis=(1, 3))


# (window, q rows, keys, tile rows, tile keys, q_offset, kv_offset): the
# census's and the offsets' cases, and a window under a step's block, one
# off the tiles' edges, one as long as the rows, a shard wholly outside
GRID_CASES = [(w, s, s, bq, bk, qo, ko)
              for w, s, bq, bk, qo, ko in CENSUS_CASES] + [
    (w, sq, sk, 16, 16, qo, ko)
    for w, sq, sk, qo, ko in OFFSET_CASES.values()] + [
    (8, 256, 256, 32, 64, 0, 0), (50, 256, 256, 32, 16, 0, 0),
    (256, 256, 256, 32, 32, 0, 0), (16, 64, 64, 16, 16, 4096, 0),
    (1024, 16384, 16384, 256, 256, 0, 0),
]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("window,sq,sk,bq,bk,q_off,kv_off", GRID_CASES)
def test_every_live_block_is_named_by_one_step_and_no_other_step_is_live(
        window, sq, sk, bq, bk, q_off, kv_off, traced):
    """Forward's and dq's grid and dkv's, with the offsets static (the
    extent is the most any resident block reaches) and traced (what a block
    can reach wherever its window starts): the live steps of a resident
    block name its live blocks, each once and in order; the others are not
    live and name a block that exists, the one before them where there is
    one (nothing is fetched)."""
    mask = fa.sliding_window_mask(window)
    static = None if traced else (q_off, kv_off)
    seen = dense_mask(window, sq, sk, q_off, kv_off)
    rows, _, keys, steps, _ = fa._kv_grid(sq, sk, bq, bk, mask, static)
    assert steps <= min(sk // keys, -(-(rows + window - 1) // keys) + 1)
    live = _live_blocks(seen, rows, keys)
    for i in range(sq // rows):
        named = [fa._window_kv_block(mask, q_off + i * rows, rows, kv_off,
                                     sk // keys, keys, j)
                 for j in range(steps)]
        assert [int(b) for b, on in named if on] \
            == list(np.flatnonzero(live[i])), i
        assert all(0 <= int(b) < sk // keys for b, _ in named)
        assert all(bool(named[j - 1][1]) or not bool(named[j][1])
                   for j in range(1, steps))
        assert all(int(named[j][0]) == int(named[j - 1][0])
                   for j in range(1, steps) if not named[j][1])
    _, rows, keys, steps = fa._q_grid(sq, sk, bq, bk, mask, static)
    assert steps <= min(sq // rows, -(-(keys + window - 1) // rows) + 1)
    live = _live_blocks(seen, rows, keys)
    for jk in range(sk // keys):
        named = [fa._window_q_block(mask, kv_off + jk * keys, keys, q_off,
                                    sq // rows, rows, i)
                 for i in range(steps)]
        assert [int(b) for b, on in named if on] \
            == list(np.flatnonzero(live[:, jk])), jk
        assert all(0 <= int(b) < sq // rows for b, _ in named)
        assert all(int(named[i][0]) == int(named[i - 1][0])
                   for i in range(1, steps) if not named[i][1])
    if not traced:
        # and the census counts those steps
        census = fa.grid_census(sq, sk, bq, bk, mask, q_off, kv_off)
        rows, _, keys, steps, _ = fa._kv_grid(sq, sk, bq, bk, mask, static)
        assert census["fwd"] == census["dq"] == {
            "launched": sq // rows * steps,
            "live": int(_live_blocks(seen, rows, keys).sum())}
        _, rows, keys, steps = fa._q_grid(sq, sk, bq, bk, mask, static)
        assert census["dkv"] == {
            "launched": sk // keys * steps,
            "live": int(_live_blocks(seen, rows, keys).sum())}


def test_a_window_as_long_as_the_keys_takes_the_causal_grid():
    """``window >= sk``: every block of keys is a step, as under the causal
    mask, and the census agrees with the causal one."""
    for window in (64, 100):
        mask = fa.sliding_window_mask(window)
        # (but for the rows a block holds: two tiles under a window)
        assert fa._kv_grid(64, 64, 16, 16, mask)[1:] \
            == fa._kv_grid(64, 64, 16, 16, fa.CAUSAL)[1:]
        assert fa._q_grid(64, 64, 16, 16, mask) \
            == fa._q_grid(64, 64, 16, 16, fa.CAUSAL)
        assert fa.grid_census(64, 64, 16, 16, mask)["dkv"] \
            == fa.grid_census(64, 64, 16, 16, True)["dkv"]


def test_the_tiles_the_window_chooses_match_dense(rng):
    """No blocks named: 512 x 512 tiles under a window of 1024, two tiles
    of rows a resident block and two of keys a grid step, each chunk of
    rows its own tiles, two steps a block on a grid of ``(1, 1, 2, 2)``;
    and the same call with its offsets traced."""
    window, s = 1024, 2048
    mask = fa.sliding_window_mask(window)
    seen = jnp.asarray(dense_mask(window, s, s))
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, s, 1, 16)).astype(np.float32))
    q, k, v, w = mk(), mk(), mk(), mk()
    assert fa._kv_grid(s, s, *fa.default_blocks(16, mask), mask, (0, 0)) \
        == (1024, 512, 1024, 2, 512)
    assert fa._kv_grid(s, s, *fa.default_blocks(16, mask), mask) \
        == (1024, 512, 1024, 2, 512)     # two blocks are all there are
    (_, want), want_grads = _out_and_grads(
        lambda q, k, v: _dense(q, k, v, seen), q, k, v, w)
    for offs in ({}, {"q_offset": jnp.int32(0), "kv_offset": jnp.int32(0)}):
        (_, out), grads = jax.jit(lambda q, k, v, offs: _out_and_grads(
            lambda q, k, v: fa.flash_attention(
                q, k, v, mask=mask, interpret=True, **offs),
            q, k, v, w))(q, k, v, offs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        for name, a, b in zip(("dq", "dk", "dv"), grads, want_grads):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5, err_msg=name)


def _counted(name, label):
    from horovod_tpu import metrics

    return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                name, {}).get("samples", [])
            if s["labels"]["mask"] == label}


def test_grid_step_counter_of_the_window_cell(monkeypatch):
    """The cell's window call, ``[1, 32, 16384, 128]`` under a window of
    1024, traced and not run: forward and dq take 32 steps a head, 31 of
    them live (the first block of rows has nothing behind it), dkv 64 and
    62 (the last block of keys has nothing ahead), where the grid over
    every block launched 128."""
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    mask = fa.sliding_window_mask(1024)
    name = "hvd_flash_grid_steps_traced_total"
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    before = _counted(name, mask.label)
    jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=mask, interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), x, x, x)
    delta = {k: v - before.get(k, 0)
             for k, v in _counted(name, mask.label).items()}
    assert delta == {(kernel, kind): 32 * n * (2 if kernel == "dkv" else 1)
                     for kernel in ("fwd", "dq", "dkv")
                     for kind, n in (("launched", 32), ("live", 31),
                                     ("idle", 1))}
    assert all(delta[k, "launched"] <= 1.5 * delta[k, "live"]
               for k in ("fwd", "dq", "dkv"))
    # the causal grid at the tiles before the window's: a step every block
    # (128) until PR 43, a step every live pair since
    assert fa.grid_census(16384, 16384, 1024, 512, fa.CAUSAL)["fwd"] == {
        "launched": 72, "live": 72}
    # with traced offsets the extent is what a block can reach, and what
    # is live is data
    traced = _counted(name, "sliding_window_w24")
    jax.eval_shape(lambda q, at: fa.mha_partial(
        q, q, q, at, at, causal=fa.sliding_window_mask(24), scale=1.0,
        block_q=16, block_k=16, interpret=True),
        jax.ShapeDtypeStruct((1, 2, 128, 8), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32))
    delta = {k: v - traced.get(k, 0)
             for k, v in _counted(name, "sliding_window_w24").items()}
    # two heads; half the 128 rows a block (four tiles of 16), whose
    # windows reach 64 + 23 keys: at most seven blocks of 16 wherever they
    # start
    assert {k: v for k, v in delta.items() if v} \
        == {("fwd", "launched"): 2 * 2 * 7}


def test_flash_tiles_counter_names_the_window(monkeypatch, rng):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read():
        return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
                for s in metrics.registry.snapshot()["metrics"].get(
                    "hvd_flash_tiles_traced_total", {}).get("samples", [])
                if s["labels"]["mask"] == "sliding_window_w24"}

    mask = fa.sliding_window_mask(24)
    x = jnp.asarray(rng.normal(size=(2, 128, 3, 8)).astype(np.float32))
    before = read()
    jax.jit(jax.grad(lambda q: fa.flash_attention(
        q, x, x, mask=mask, block_q=16, block_k=16,
        interpret=True).sum()))(x)
    delta = {k: v - before.get(k, 0) for k, v in read().items()}
    census = fa.tile_census(128, 128, 16, 16, mask)
    # a block's rows see three tiles (two for the second, one for the first)
    assert census == {"skipped": 64 - 21, "full": 0, "crossed": 21}
    for kernel in ("fwd", "dq", "dkv"):
        assert {kind: delta[(kernel, kind)] for kind in census} == {
            kind: 6 * n for kind, n in census.items()}
