"""The three flash kernels at a v head size apart from the q.k head size
(latent attention scores 192 columns and weighs 128), interpreter path on
the CPU: forward, dq, dk and dv against the materialised softmax, causal
and unmasked, through the custom VJP and through the ring's building
blocks with offsets; the census and the residuals' counter follow v's
width; equal sizes keep the shapes they had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             softmax_attention, tile_census)

#: (sequence, heads, q.k head size, v head size, block_q, block_k)
SHAPES = [(64, 3, 24, 16, 16, 16), (96, 2, 48, 32, 32, 16),
          (128, 1, 16, 40, 64, 32), (64, 2, 192, 128, 32, 32)]


def _qkv(rng, s, h, dk, dv, b=2):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return mk(b, s, h, dk), mk(b, s, h, dk), mk(b, s, h, dv), mk(b, s, h, dv)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_forward_and_three_gradients_match_the_softmax(rng, shape, causal):
    s, h, dk, dv, bq, bk = shape
    q, k, v, cot = _qkv(rng, s, h, dk, dv)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(cot * fn(q, k, v, causal=causal)),
            argnums=(0, 1, 2))(q, k, v)

    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    assert out.shape == (2, s, h, dv) and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(softmax_attention(q, k, v,
                                                      causal=causal)),
        atol=2e-5)
    got = both(lambda *a, **kw: flash_attention(*a, block_q=bq, block_k=bk,
                                                **kw))
    want = both(softmax_attention)
    for name, a, b in zip(("sum", "dq", "dk", "dv"),
                          jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-4, err_msg=name)
    # dq and dk are as wide as q and k, dv as v
    assert got[1][0].shape[-1] == got[1][1].shape[-1] == dk
    assert got[1][2].shape[-1] == dv


def test_the_default_scale_is_of_the_qk_head_size(rng):
    q, k, v, _ = _qkv(rng, 32, 2, 24, 16)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(softmax_attention(q, k, v, causal=True,
                                     scale=24 ** -0.5)), atol=2e-5)


@pytest.mark.parametrize("q_offset,kv_offset", [(0, 0), (64, 0), (64, 32)])
def test_the_rings_building_blocks_take_vs_width(rng, q_offset, kv_offset):
    """``mha_partial`` / ``mha_bwd_dq`` / ``mha_bwd_dkv`` on ``[b, h, s,
    d]`` shards with global offsets: the triple normalised is the softmax
    over the keys the rows see, and the two backward blocks give its
    gradients."""
    b, h, s, dk, dv = 1, 2, 64, 24, 16
    q, k, v, do = (jnp.swapaxes(t, 1, 2) for t in _qkv(rng, s, h, dk, dv,
                                                       b=b))
    scale = dk ** -0.5
    kw = dict(causal=True, scale=scale, block_q=16, block_k=16)
    o, m, l = flash.mha_partial(q, k, v, q_offset, kv_offset, **kw)
    assert o.shape == (b, h, s, dv)

    def dense(q, k, v):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        seen = (q_offset + jnp.arange(s))[:, None] \
            >= (kv_offset + jnp.arange(s))[None, :]
        logits = jnp.where(seen, logits, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(logits, axis=-1), v)

    out = o / l
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               atol=2e-5)
    lse = m + jnp.log(l)
    delta = jnp.sum(do * out, axis=-1, keepdims=True)
    dq = flash.mha_bwd_dq(q, k, v, do, lse, delta, q_offset, kv_offset, **kw)
    dk_, dv_ = flash.mha_bwd_dkv(q, k, v, do, lse, delta, q_offset,
                                 kv_offset, **kw)
    want = jax.grad(lambda *a: jnp.sum(do * dense(*a)), argnums=(0, 1, 2))(
        q, k, v)
    for a, w in zip((dq, dk_, dv_), want):
        assert a.shape == w.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=5e-5,
                                   rtol=1e-4)


def test_equal_head_sizes_keep_their_shapes(rng):
    q, k, v, _ = _qkv(rng, 32, 2, 16, 16)
    assert flash_attention(q, k, v, causal=True).shape == q.shape


def _samples(name):
    return metrics.registry.snapshot()["metrics"].get(name, {}).get(
        "samples", [])


def test_the_census_and_the_residuals_follow_vs_width(monkeypatch):
    """At the benchmark's ``kanana2-8k`` call, ``[1, 8192, 32, 192 / 128]``
    bfloat16 (traced, nothing runs): the tiles are those of any causal
    call of 8192 rows whatever the head sizes, and what a recomputed layer
    keeps is ``o`` at v's width and ``lse``: 67.1 MB + 1 MB."""
    monkeypatch.setattr(metrics.registry, "enabled", True)
    block_q, block_k = flash.default_blocks(192)
    census = tile_census(8192, 8192, block_q, block_k, True)
    assert census == tile_census(8192, 8192, *flash.default_blocks(192),
                                 flash.CAUSAL)
    assert sum(census.values()) == (8192 // block_q) * (8192 // block_k)
    assert census["full"] + census["crossed"] \
        == sum(-(-(i + 1) * block_q // block_k)
               for i in range(8192 // block_q))

    def read():
        tiles = {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
                 for s in _samples("hvd_flash_tiles_traced_total")
                 if s["labels"]["mask"] == "causal"}
        kept = {s["labels"]["kernel"]: s["value"] for s in _samples(
            "hvd_kernel_residual_bytes_traced_total")}
        return tiles, kept.get("flash", 0)

    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    tiles_before, kept_before = read()
    jax.eval_shape(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), qk, qk, v)
    tiles, kept = read()
    assert kept - kept_before == 8192 * 32 * 128 * 2 + 8192 * 32 * 4
    for kernel in ("fwd", "dq", "dkv"):
        for kind, n in census.items():
            assert tiles[kernel, kind] - tiles_before.get(
                (kernel, kind), 0) == 32 * n, (kernel, kind)


def test_default_blocks_at_latent_attentions_head_size():
    """Head size 192 takes the tiles swept for it (PR 34), the accepted
    cells' head sizes the ones they had."""
    assert flash.default_blocks(64) == flash.default_blocks(128) \
        == (1024, 512)
    assert flash.default_blocks(256) == (512, 512)
    assert flash.default_blocks(192) == (1024, 512)
