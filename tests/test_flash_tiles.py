"""The flash kernels where the causal diagonal decides what a grid step
does: tiles that are skipped, seen whole or crossed, offsets that move the
diagonal off the block grid, the moved logit scale, and the trace-time tile
counter.  Interpreter mode, forward and the gradients of all three kernels
against a dense reference.  A file of its own beside
test_flash_attention.py: pytest-xdist hands files out by their number of
tests, and these cases in that file moved its interpreted kernels under
two timing tests of the control plane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


@pytest.fixture(autouse=True)
def _on_cpu():
    """Exact f32 on the CPU whatever backends are present (as in
    test_flash_attention.py)."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def _dense_jnp(q, k, v, causal, q_off=0, kv_off=0, scale=None):
    """Reference with global-position masking; a row that sees no key
    comes out zero (and passes no gradient), as the kernels leave it."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if not causal:
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    seen = ((q_off + jnp.arange(q.shape[1]))[:, None]
            >= (kv_off + jnp.arange(k.shape[1]))[None, :])[None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1) * seen
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# four tiles a side and four tiles a grid step: (sq, sk, tile, head_dim,
# causal, q_offset, kv_offset, scale).  Tiles of 128 keep the forward's row
# sums lane-partial, tiles of 64 reduce them every step.
TILE_CASES = {
    # skipped, full and crossed tiles in one call
    "aligned": (512, 512, 128, 16, True, 0, 0, None),
    # every tile full: the unmasked body alone
    "all_full": (256, 256, 64, 16, True, 256, 0, None),
    # every tile skipped: zeros, finite, no gradient
    "all_skipped": (256, 256, 64, 16, True, 0, 256, None),
    # crossed tiles off the block diagonal
    "unaligned": (512, 512, 128, 16, True, 64, 0, None),
    # rows masked whole inside crossed tiles
    "rows_before_kv": (256, 256, 64, 16, True, 0, 32, None),
    # a kv block of four tiles with the diagonal inside the first
    "diag_in_first_tile": (64, 256, 64, 16, True, 0, 0, None),
    # the diagonal inside the last tile of a block: full tiles, then one
    "diag_in_last_tile": (64, 256, 64, 16, True, 192, 0, None),
    # a scale that is no power of two stays on the float32 scores
    "scale_0.3": (256, 256, 64, 16, True, 0, 0, 0.3),
    "head_96": (256, 256, 64, 96, True, 0, 0, None),
    "full_scale_0.3": (256, 256, 64, 16, False, 0, 0, 0.3),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_flash_tile_kinds_match_dense(rng, case):
    """Forward and the gradients of all three kernels where tiles are
    skipped, full or crossed by the diagonal, and with the scale moved."""
    sq, sk, tile, d, causal, q_off, kv_off, scale = TILE_CASES[case]
    mk = lambda s: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, s, 1, d)).astype(np.float32))
    q, k, v, w = mk(sq), mk(sk), mk(sk), mk(sq)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, scale=scale, q_offset=q_off,
        kv_offset=kv_off, block_q=tile, block_k=tile, interpret=True)
    dense = lambda q, k, v: _dense_jnp(  # noqa: E731
        q, k, v, causal, q_off, kv_off, scale)

    def out_and_grads(f):
        """One compiled program a side: the output rides as the aux."""
        def loss(q, k, v):
            out = f(q, k, v)
            return (out * w).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out, *grads)

    got, want = out_and_grads(flash), out_and_grads(dense)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    if case == "all_skipped":
        for a in got:
            np.testing.assert_array_equal(np.asarray(a), 0.0)


def test_flash_tiles_counter_counts_once_a_traced_call(monkeypatch, rng):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)

    def read():
        got = {}
        for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_flash_tiles_traced_total", {}).get("samples", []):
            if s["labels"]["mask"] == "causal":
                got[(s["labels"]["kernel"], s["labels"]["kind"])] = s["value"]
        return got

    x = jnp.asarray(rng.normal(size=(2, 256, 3, 8)).astype(np.float32))
    fn = jax.jit(jax.grad(lambda q: flash_attention(
        q, x, x, causal=True, block_q=64, block_k=64,
        interpret=True).sum()))
    before = read()
    fn(x)
    fn(x)  # a cache hit: the counter moves per trace, not per call
    delta = {k: v - before.get(k, 0) for k, v in read().items()}
    # 4 x 4 tiles a head, 2 x 3 heads: 6 skipped, 6 full, 4 crossed each
    for kernel in ("fwd", "dq", "dkv"):
        assert {kind: delta.get((kernel, kind), 0)
                for kind in ("skipped", "full", "crossed", "dynamic")} == {
            "skipped": 36, "full": 36, "crossed": 24, "dynamic": 0}
    # traced offsets: the kind cannot be known, the grid's tiles are counted
    before = read()
    jax.jit(lambda o: flash_attention(
        x, x, x, causal=True, q_offset=o, block_q=64, block_k=64,
        interpret=True))(jnp.int32(0))
    delta = {k: v - before.get(k, 0) for k, v in read().items()}
    assert delta.get(("fwd", "dynamic"), 0) == 2 * 3 * 16
