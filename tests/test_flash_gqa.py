"""The flash kernels with k and v at their own head count: q has ``h = g *
hk`` heads, a q head ``i`` reads kv head ``i // g`` through the kv blocks'
index maps, and dkv's grid runs over the kv heads and streams a group's q
heads through one resident block of keys, so dk and dv leave the kernel
summed over the group.  Output, dq, dk and dv in interpreter mode against
the same call on k and v repeated to the q heads (the form the models had)
and against a dense softmax, under every mask, on the table and on the
rectangle; the table's fourth row; the counter that says a call was grouped;
and the grids as Mosaic gets them."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa

from test_flash_block_diffusion import dense_mask as bd_dense_mask
from test_flash_grid import _mosaic_bodies
from test_flash_window import dense_mask as window_dense_mask


@pytest.fixture(autouse=True)
def _on_cpu():
    """Exact f32 on the CPU whatever backends are present (as in
    test_flash_attention.py)."""
    with jax.default_device(jax.devices("cpu")[0]):
        yield


S, HEADS, BLOCKS = 256, 8, dict(block_q=64, block_k=32)
MASKS = {
    "causal": fa.CAUSAL,
    "window": fa.sliding_window_mask(80),
    "block_diffusion": fa.block_diffusion_mask(4, S // 2),
    "none": fa.NO_MASK,
}


def _seen(mask, sq, sk, q_off=0, kv_off=0):
    """Which pairs ``mask`` allows, ``[sq, sk]`` booleans, pair by pair."""
    if mask.kind == "block_diffusion":
        return bd_dense_mask(mask.block, mask.noised)
    if mask.kind == "sliding_window":
        return window_dense_mask(mask.window, sq, sk, q_off, kv_off)
    if mask.kind == "causal":
        return ((q_off + np.arange(sq))[:, None]
                >= (kv_off + np.arange(sk))[None, :])
    return np.ones((sq, sk), bool)


def _dense(q, k, v, seen):
    """Softmax attention over equal head counts under a dense mask."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _operands(rng, group, d, dv, sq=S, sk=S, h=HEADS, b=1):
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape).astype(np.float32))
    return (mk(b, sq, h, d), mk(b, sk, h // group, d),
            mk(b, sk, h // group, dv), mk(b, sq, h, dv))


def _out_and_grads(attend, q, k, v, w, repeat=1):
    """``(o, dq, dk, dv)`` of ``attend`` on k and v as they come, or first
    repeated ``repeat`` times a head (the gradients are then k's and v's
    own: the repeat's transpose sums a group)."""
    def loss(q, k, v):
        if repeat > 1:
            k, v = (jnp.repeat(t, repeat, axis=2) for t in (k, v))
        o = attend(q, k, v)
        return jnp.sum(o * w), o

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return [np.asarray(x) for x in (out, *grads)]


def _check(grouped, repeated, reference):
    for name, a, b, c in zip(("o", "dq", "dk", "dv"), grouped, repeated,
                             reference):
        assert a.shape == b.shape == c.shape, name
        assert np.isfinite(a).all(), name
        if name in ("o", "dq"):
            # a q head does the arithmetic it did on the tiles it had
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            # a group's sum in the accumulator, not after a cast a head
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                       err_msg=name)
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("group", [2, 4, 8])
@pytest.mark.parametrize("kind", sorted(MASKS))
def test_a_kv_head_serves_its_group(rng, kind, group, head):
    """k and v at ``8 // group`` heads give what k and v repeated to 8 give,
    and what a dense softmax gives."""
    mask = MASKS[kind]
    q, k, v, w = _operands(rng, group, head, head)
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, mask=mask, interpret=True, **BLOCKS)
    grouped = _out_and_grads(flash, q, k, v, w)
    assert grouped[2].shape == k.shape and grouped[3].shape == v.shape
    repeated = _out_and_grads(flash, q, k, v, w, repeat=group)
    if kind in ("causal", "none"):
        dense = lambda q, k, v: fa.softmax_attention(  # noqa: E731
            q, k, v, causal=kind == "causal")
    else:
        seen = jnp.asarray(_seen(mask, S, S))
        dense = lambda q, k, v: _dense(q, k, v, seen)  # noqa: E731
    _check(grouped, repeated, _out_and_grads(dense, q, k, v, w,
                                             repeat=group))


@pytest.mark.parametrize("group", [2, 4])
@pytest.mark.parametrize("d,dv", [(64, 32), (24, 16)])
def test_vs_head_size_is_its_own_under_a_group(rng, d, dv, group):
    """Latent attention's widths (q.k wider than v) with fewer kv heads:
    dk is as wide as k and dv as v, each at ``hk`` heads."""
    q, k, v, w = _operands(rng, group, d, dv)
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=True, **BLOCKS)
    dense = lambda q, k, v: fa.softmax_attention(  # noqa: E731
        q, k, v, causal=True)
    grouped = _out_and_grads(flash, q, k, v, w)
    assert [x.shape[-2:] for x in grouped] == [
        (HEADS, dv), (HEADS, d), (HEADS // group, d), (HEADS // group, dv)]
    _check(grouped, _out_and_grads(flash, q, k, v, w, repeat=group),
           _out_and_grads(dense, q, k, v, w, repeat=group))


# name: (mask, (q_offset, kv_offset), sq, sk): offsets on the blocks and off
# them, rows before every key, lengths that differ
OFFSET_CASES = {
    "causal_at_zero": (fa.CAUSAL, (0, 0), 256, 256),
    "causal_queries_ahead": (fa.CAUSAL, (128, 0), 256, 256),
    "causal_off_the_blocks": (fa.CAUSAL, (100, 37), 256, 256),
    "causal_short_queries": (fa.CAUSAL, (200, 0), 128, 384),
    "window_keys_ahead": (fa.sliding_window_mask(80), (0, 64), 256, 256),
    "window_off_the_blocks": (fa.sliding_window_mask(48), (100, 37), 256,
                              256),
}


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("case", sorted(OFFSET_CASES))
def test_a_group_on_the_table_and_on_the_rectangle(rng, case, traced):
    """Static offsets take the flattened grid where the mask is causal (a
    group's members pass through a resident block's pairs in turn), traced
    ones the rectangle (``group`` times the steps), a window its fitted grid
    either way: all give what the repeated call gives at the same offsets,
    and what the dense softmax gives."""
    mask, offs, sq, sk = OFFSET_CASES[case]
    group = 4
    q, k, v, w = _operands(rng, group, 32, 32, sq=sq, sk=sk)

    def flash(q, k, v):
        if not traced:
            return fa.flash_attention(
                q, k, v, mask=mask, interpret=True, q_offset=offs[0],
                kv_offset=offs[1], **BLOCKS)
        # the offsets are arguments of a program: tracers in the launchers
        return jax.jit(lambda q, k, v, a, b: fa.flash_attention(
            q, k, v, mask=mask, interpret=True, q_offset=a, kv_offset=b,
            **BLOCKS))(q, k, v, *map(jnp.int32, offs))

    seen = jnp.asarray(_seen(mask, sq, sk, *offs))
    # a row that sees no key: the kernels give 0, the dense softmax a mean
    rows = np.asarray(seen).any(axis=1)
    w = w * jnp.asarray(rows, jnp.float32)[None, :, None, None]
    dense = lambda q, k, v: _dense(q, k, v, seen)  # noqa: E731
    _check(_out_and_grads(flash, q, k, v, w),
           _out_and_grads(flash, q, k, v, w, repeat=group),
           [x * (rows[None, :, None, None] if n < 2 else 1)
            for n, x in enumerate(_out_and_grads(dense, q, k, v, w,
                                                 repeat=group))])


@pytest.mark.parametrize("group", [2, 8])
@pytest.mark.parametrize("mask", [fa.CAUSAL, fa.block_diffusion_mask(4, 512)],
                         ids=lambda m: m.kind)
def test_the_table_takes_a_blocks_pairs_once_a_member(mask, group):
    """dkv's table under a group: a resident block's pairs ``group`` times
    over, the members in turn, between one first and one last step; the
    fourth row is the member; the census goes by q heads as it did."""
    sq = sk = 1024
    _, rows, keys, steps = fa._q_grid(sq, sk, 64, 32, mask, (0, 0))
    args = (fa._q_blocks_seen, steps, mask, sq, sk, rows, keys, (0, 0))
    resident, streamed, edge = fa._pair_table(*args).tolist()
    table = fa._pair_table(*args, group)
    assert table.shape == (4, group * len(resident))
    want = []
    for block in sorted(set(resident)):
        live = [s for r, s in zip(resident, streamed) if r == block]
        passes = [(block, s, m) for m in range(group) for s in live]
        want += [(*p, (n == 0) + 2 * (n == len(passes) - 1))
                 for n, p in enumerate(passes)]
    got = table.tolist()
    assert list(zip(got[0], got[1], got[3], got[2])) == want
    assert sum(e & 1 for e in got[2]) == sum(e >= 2 for e in got[2]) \
        == len(set(resident))
    assert fa.grid_census(sq, sk, 64, 32, mask, group=group) \
        == fa.grid_census(sq, sk, 64, 32, mask)
    # one head a kv head: no fourth row, the table it was
    np.testing.assert_array_equal(fa._pair_table(*args, 1),
                                  np.array([resident, streamed, edge]))


def test_a_groups_table_past_scalar_memory_keeps_the_rectangle(monkeypatch):
    """The bound is on the words the table takes of scalar memory: four a
    step of a group's, three a step without one."""
    sq = sk = 1024
    _, rows, keys, steps = fa._q_grid(sq, sk, 64, 32, fa.CAUSAL, (0, 0))
    args = (fa._q_blocks_seen, steps, fa.CAUSAL, sq, sk, rows, keys, (0, 0))
    pairs = fa._pair_table(*args).shape[1]
    monkeypatch.setattr(fa, "MAX_PAIRS", pairs * 3)
    assert fa._pair_table(*args, 2) is not None
    assert fa._pair_table(*args, 4) is None
    blocks = sk // keys
    census = fa.grid_census(sq, sk, 64, 32, fa.CAUSAL, group=4)["dkv"]
    assert census["launched"] == blocks * steps > census["live"] == pairs


@pytest.mark.parametrize("shapes", [
    ((1, 64, 6, 16), (1, 64, 4, 16), (1, 64, 4, 16)),
    ((1, 64, 8, 16), (1, 64, 3, 16), (1, 64, 3, 16)),
    ((1, 64, 8, 16), (1, 64, 4, 16), (1, 64, 2, 16)),
], ids=["6_over_4", "8_over_3", "k4_v2"])
def test_heads_that_do_not_share_evenly_are_refused(shapes):
    q, k, v = (jnp.zeros(shape, jnp.float32) for shape in shapes)
    heads = [str(x.shape[2]) for x in (q, k, v)]
    with pytest.raises(ValueError) as err:
        fa.flash_attention(q, k, v, causal=True, interpret=True)
    assert all(n in str(err.value) for n in heads)
    at = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    with pytest.raises(ValueError):
        fa.mha_partial(at(q), at(k), at(v), 0, 0, causal=True, scale=1.0,
                       interpret=True)


def test_the_ring_blocks_take_a_group_too(rng):
    """``mha_partial`` / ``mha_bwd_dq`` / ``mha_bwd_dkv`` (``[b, h, s, d]``,
    traced offsets): dk and dv come back float32 at ``hk`` heads, the sums
    of what the call at equal heads gives a q head."""
    group, h, s, d = 4, 8, 128, 16
    q, k, v, do = (jnp.swapaxes(x, 1, 2)
                   for x in _operands(rng, group, d, d, sq=s, sk=s, h=h))
    kw = dict(causal=True, scale=d ** -0.5, block_q=32, block_k=32,
              interpret=True)

    @jax.jit
    def blocks(q, k, v, do, at):
        o, m, l = fa.mha_partial(q, k, v, at, at, **kw)
        lse = m + jnp.log(l)
        delta = jnp.sum(do * o / l, axis=-1, keepdims=True)
        return (o, fa.mha_bwd_dq(q, k, v, do, lse, delta, at, at, **kw),
                *fa.mha_bwd_dkv(q, k, v, do, lse, delta, at, at, **kw))

    o, dq, dk, dv = blocks(q, k, v, do, jnp.int32(0))
    o_r, dq_r, dk_r, dv_r = blocks(q, jnp.repeat(k, group, axis=1),
                                   jnp.repeat(v, group, axis=1), do,
                                   jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_r))
    np.testing.assert_array_equal(np.asarray(dq), np.asarray(dq_r))
    assert dk.shape == k.shape and dv.shape == v.shape
    assert dk.dtype == dv.dtype == jnp.float32
    for got, whole in ((dk, dk_r), (dv, dv_r)):
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(whole).reshape(1, h // group, group, s, d).sum(2),
            rtol=2e-5, atol=2e-5)


def _kv_groups():
    from horovod_tpu import metrics

    return {(s["labels"]["kernel"], s["labels"]["q_heads"],
             s["labels"]["kv_heads"]): s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_flash_kv_group_traced_total", {}).get("samples", [])}


@pytest.mark.parametrize("h,hk", [(8, 2), (3, 3)])
def test_the_counter_says_which_calls_were_grouped(monkeypatch, rng, h, hk):
    """One a traced kernel call, by the two head counts; a cache hit counts
    nothing."""
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    q, k, v, _ = _operands(rng, h // hk, 8, 8, sq=128, sk=128, h=h)
    fn = jax.jit(jax.grad(lambda q: fa.flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True).sum()))
    before = _kv_groups()
    fn(q)
    fn(q)
    delta = {key: n - before.get(key, 0) for key, n in _kv_groups().items()
             if n != before.get(key, 0)}
    assert delta == {(kernel, str(h), str(hk)): 1
                     for kernel in ("fwd", "dq", "dkv")}


def _bounds(body):
    return [int(n) for n in re.search(
        r"iteration_bounds = array<i64: ([\d, ]+)>", body).group(1).split(",")]


@pytest.mark.parametrize("mask", [fa.CAUSAL, fa.block_diffusion_mask(4, 4096)],
                         ids=lambda m: m.kind)
def test_mosaic_gets_dkv_over_the_kv_heads_on_the_table(mask, monkeypatch):
    """Forward and dq keep ``(b, h, pairs)`` and three words a pair; dkv runs
    ``(b, hk, g * pairs)`` on four."""
    h, hk, s = 8, 2, 8192
    bodies = _mosaic_bodies(monkeypatch, dict(mask=mask), False, s=s,
                            heads=h, kv_heads=hk)
    steps = fa.grid_census(s, s, *fa.default_blocks(128, mask), mask)
    for kernel, body in zip(("fwd", "dq"), bodies):
        pairs = steps[kernel]["launched"]
        assert _bounds(body) == [1, h, pairs]
        assert f"memref<{2 + 3 * pairs}xi32, #tpu.memory_space<smem>>" in body
        # k's and v's index maps: the q head over the group
        maps = body[body.index("func.func @transform_0"):]
        assert maps.count("arith.divsi") == 2
    pairs = steps["dkv"]["launched"] * (h // hk)
    assert _bounds(bodies[2]) == [1, hk, pairs]
    assert f"memref<{2 + 4 * pairs}xi32, #tpu.memory_space<smem>>" \
        in bodies[2]
    # q's, do's, lse's and delta's index maps: the kv head times the group
    # plus the step's member
    maps = bodies[2][bodies[2].index("func.func @transform_0"):]
    assert maps.count("arith.muli") == 4


def test_mosaic_gets_dkv_over_the_kv_heads_on_the_windows_grid(monkeypatch):
    """The fitted grid of a window, ``group`` times the steps a block of
    keys takes; forward and dq keep theirs."""
    h, hk, s = 8, 2, 4096
    mask = fa.sliding_window_mask(1024)
    bodies = _mosaic_bodies(monkeypatch, dict(mask=mask), False, s=s,
                            heads=h, kv_heads=hk)
    block_q, block_k = fa.default_blocks(128, mask)
    rows, _, keys, kv_steps, _ = fa._kv_grid(s, s, block_q, block_k, mask,
                                             (0, 0))
    for body in bodies[:2]:
        assert _bounds(body) == [1, h, s // rows, kv_steps]
    _, rows, keys, steps = fa._q_grid(s, s, block_q, block_k, mask, (0, 0))
    assert _bounds(bodies[2]) == [1, hk, s // keys, (h // hk) * steps]
    assert all("xi32, #tpu.memory_space<smem>>" in body
               and "memref<2xi32, #tpu.memory_space<smem>>" in body
               for body in bodies)
