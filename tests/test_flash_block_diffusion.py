"""The flash kernels under the block-diffusion mask: forward and all three
gradients in interpreter mode against a dense-mask softmax, ``tile_census``
against a brute-force count over the mask, the traced tile counter.  The
rows are ``[noised copy ; clean copy]``; a clean row sees the clean blocks
up to its own, a noised row the clean blocks before its own and the noised
tokens of its own block."""

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


def dense_mask(block: int, noised: int) -> np.ndarray:
    """The definition, pair by pair: ``[2 noised, 2 noised]`` booleans."""
    i = np.arange(2 * noised)
    c, g = i // noised, (i % noised) // block
    return ((c[None, :] == 1) & (g[None, :] < g[:, None] + c[:, None])) | (
        (c[:, None] == 0) & (c[None, :] == 0) & (g[None, :] == g[:, None]))


def test_the_mask_allows_l_squared_plus_l_b_pairs():
    for block, noised in ((4, 64), (12, 96), (32, 64)):
        assert dense_mask(block, noised).sum() == noised ** 2 + noised * block


def _dense(q, k, v, seen):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (block, noised rows, tile rows, tile keys): a block that divides the tile,
# one that does not (blocks of 12 over tiles of 16 and 32), one larger than
# the tile (whole tiles inside the block-diagonal quadrant), and one tile a
# copy (a step's kv block is one tile)
BD_CASES = {
    "b4": (4, 64, 16, 16),
    "b4_wide_rows": (4, 128, 32, 16),
    "b12_off_the_tiles": (12, 96, 32, 16),
    "b32_over_the_tile": (32, 128, 16, 16),
    "one_tile_a_copy": (4, 32, 32, 32),
}


@pytest.mark.parametrize("case", sorted(BD_CASES))
def test_flash_block_diffusion_matches_dense(rng, case):
    block, noised, bq, bk = BD_CASES[case]
    mask = fa.block_diffusion_mask(block, noised)
    seen = jnp.asarray(dense_mask(block, noised))
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, 2 * noised, 2, 16)).astype(np.float32))
    q, k, v, w = mk(), mk(), mk(), mk()
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, mask=mask, block_q=bq, block_k=bk, interpret=True)

    def out_and_grads(f):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (jnp.sum(f(q, k, v) * w), f(q, k, v)),
            argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, out), grads = out_and_grads(flash)
    (_, want), want_grads = out_and_grads(
        lambda q, k, v: _dense(q, k, v, seen))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


CENSUS_CASES = [
    # block, noised rows, block_q, block_k
    (4, 8192, 512, 512),
    (4, 8192, 1024, 512),
    (4, 64, 16, 16),
    (12, 96, 32, 16),
    (12, 96, 16, 32),
    (32, 128, 16, 16),
    (64, 128, 16, 32),
    (8, 64, 64, 64),
    (1, 32, 8, 8),
]


@pytest.mark.parametrize("block,noised,bq,bk", CENSUS_CASES)
def test_tile_census_matches_the_block_diffusion_mask(block, noised, bq, bk):
    mask = fa.block_diffusion_mask(block, noised)
    s = 2 * noised
    fq, fk = fa._check_blocks(s, s, bq, bk, mask)    # as the kernels fit
    assert noised % fq == 0 and noised % fk == 0     # no tile in two copies
    tiles = dense_mask(block, noised).reshape(s // fq, fq, s // fk, fk)
    every, some = tiles.all(axis=(1, 3)), tiles.any(axis=(1, 3))
    assert fa.tile_census(s, s, bq, bk, mask) == {
        "skipped": int((~some).sum()), "full": int(every.sum()),
        "crossed": int((some & ~every).sum())}


def test_tile_census_of_the_block_diffusion_cell():
    """ISSUE 30: 2 x 8192 rows at 512 x 512 tiles visit 288 of 1024, 48 of
    them crossed; a causal mask over the same rows visits 528."""
    mask = fa.block_diffusion_mask(4, 8192)
    assert fa.tile_census(16384, 16384, 512, 512, mask) == {
        "skipped": 736, "full": 240, "crossed": 48}
    assert fa.tile_census(16384, 16384, 512, 512, True) == {
        "skipped": 496, "full": 496, "crossed": 32}
    # at the tiles the kernels run by default for head size 128
    assert fa.default_blocks(128) == (1024, 512)
    assert fa.tile_census(16384, 16384, 1024, 512, mask) == {
        "skipped": 352, "full": 112, "crossed": 48}


@pytest.mark.parametrize("block,noised,bq,bk", CENSUS_CASES[2:])
def test_what_dkv_skips_is_what_the_mask_hides(block, noised, bq, bk):
    """dkv's range of query tiles for a block of keys, and the block a
    skipped step of either side names, against the mask."""
    mask = fa.block_diffusion_mask(block, noised)
    s = 2 * noised
    fq, fk = fa._check_blocks(s, s, bq, bk, mask)
    some = dense_mask(block, noised).reshape(
        s // fq, fq, s // fk, fk).any(axis=(1, 3))
    n = noised // fq
    for jk in range(s // fk):
        for part in range(2):
            lo, hi = fa._q_tiles_seen(mask, jk * fk, fk, part * noised, n,
                                      fq)
            want = np.flatnonzero(some[part * n:(part + 1) * n, jk])
            assert list(range(int(lo), int(hi))) == list(want), (jk, part)


def test_a_skipped_step_names_a_live_block():
    live = [(2, 4), (6, 8)]
    got = [int(fa._nearest_live(jnp.int32(i), live)) for i in range(8)]
    assert got == [2, 2, 2, 3, 6, 6, 6, 7]
    assert [int(fa._nearest_live(jnp.int32(i), [(4, 4), (5, 7)]))
            for i in range(8)] == [5, 5, 5, 5, 5, 5, 6, 6]
    assert [int(fa._nearest_live(jnp.int32(i), [(1, 2), (4, 4)]))
            for i in range(4)] == [1, 1, 1, 1]


def test_block_diffusion_mask_refuses_what_it_cannot_tile():
    with pytest.raises(ValueError, match="do not tile"):
        fa.block_diffusion_mask(12, 64)
    mask = fa.block_diffusion_mask(4, 64)
    x = jnp.zeros((1, 64, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="128 queries and keys"):
        fa.flash_attention(x, x, x, mask=mask, interpret=True)
    x = jnp.zeros((1, 128, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="no offsets"):
        fa.flash_attention(x, x, x, mask=mask, q_offset=4, interpret=True)


def test_flash_tiles_counter_names_the_mask(monkeypatch, rng):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read():
        return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
                for s in metrics.registry.snapshot()["metrics"].get(
                    "hvd_flash_tiles_traced_total", {}).get("samples", [])
                if s["labels"]["mask"] == "block_diffusion_b4"}

    mask = fa.block_diffusion_mask(4, 64)
    x = jnp.asarray(rng.normal(size=(2, 128, 3, 8)).astype(np.float32))
    before = read()
    jax.jit(jax.grad(lambda q: fa.flash_attention(
        q, x, x, mask=mask, block_q=16, block_k=16,
        interpret=True).sum()))(x)
    delta = {k: v - before.get(k, 0) for k, v in read().items()}
    census = fa.tile_census(128, 128, 16, 16, mask)
    assert census == {"skipped": 40, "full": 12, "crossed": 12}
    for kernel in ("fwd", "dq", "dkv"):
        assert {kind: delta[(kernel, kind)] for kind in census} == {
            kind: 6 * n for kind, n in census.items()}
