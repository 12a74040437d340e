"""``parallel.moe.routed_experts``: top-k routing over the experts held
here with nothing dropped, against a dense numpy oracle; the share test
(the parts of all the shares add up to the uncut layer); a skewed router;
tiles of several sizes; ``load_census`` against a hand count; several
groups of rows in one call against the dense oracle a group, and what the
backward pass carries through them."""

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

D, F, E, TOP_K, N = 16, 8, 32, 4, 96


def _layer(rng, experts=E):
    return {
        "router": rng.normal(size=(D, experts)).astype(np.float32),
        "gate_proj": rng.normal(size=(experts, D, F)).astype(np.float32) * .4,
        "up_proj": rng.normal(size=(experts, D, F)).astype(np.float32) * .4,
        "down_proj": rng.normal(size=(experts, F, D)).astype(np.float32) * .4,
    }


def _share(layer, first, held):
    return {k: jnp.asarray(layer[k][first:first + held])
            for k in ("gate_proj", "up_proj", "down_proj")}


def _oracle(layer, x, first=0, held=None):
    """Token by token in float64 numpy: softmax over all experts, the
    ``TOP_K`` largest, weights over their sum; only the experts in
    ``[first, first + held)`` add anything."""
    experts = layer["router"].shape[1]
    held = experts if held is None else held
    x = np.asarray(x, np.float64)
    logits = x @ layer["router"].astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        picks = np.argsort(-p[t], kind="stable")[:TOP_K]
        total = p[t, picks].sum()
        for e in picks:
            if first <= e < first + held:
                g = x[t] @ layer["gate_proj"][e].astype(np.float64)
                u = x[t] @ layer["up_proj"][e].astype(np.float64)
                h = g / (1.0 + np.exp(-g)) * u
                out[t] += p[t, e] / total * (
                    h @ layer["down_proj"][e].astype(np.float64))
    return out


def _run(layer, x, first, held):
    return np.asarray(moe.routed_experts(
        jnp.asarray(x), jnp.asarray(layer["router"]),
        _share(layer, first, held), top_k=TOP_K, first_expert=first))


@pytest.fixture
def tile(request, monkeypatch):
    """``moe.TILE`` set to the test's parameter: the toy layers have a few
    dozen rows an expert, so small tiles split them as the real one splits
    hundreds."""
    monkeypatch.setattr(moe, "TILE", request.param)
    return request.param


@pytest.mark.parametrize("first,held", [(0, 32), (0, 4), (12, 4), (28, 4)])
def test_held_experts_part_matches_the_dense_oracle(rng, first, held):
    layer = _layer(rng)
    x = rng.normal(size=(N, D)).astype(np.float32)
    np.testing.assert_allclose(_run(layer, x, first, held),
                               _oracle(layer, x, first, held),
                               rtol=2e-4, atol=2e-5)


def test_the_parts_of_all_shares_add_up_to_the_uncut_layer(rng):
    """Section 4 of the model-configs guide: eight shares of four experts
    each, routed over all 32 with the weights normalised over all the
    picks, sum to the whole layer; what every chip computes alike (a
    shared expert) would be counted once beside them."""
    layer = _layer(rng)
    x = rng.normal(size=(N, D)).astype(np.float32)
    parts = sum(_run(layer, x, first, 4) for first in range(0, E, 4))
    np.testing.assert_allclose(parts, _oracle(layer, x), rtol=2e-4,
                               atol=4e-5)
    np.testing.assert_allclose(parts, _run(layer, x, 0, E), rtol=2e-4,
                               atol=4e-5)


@pytest.mark.parametrize("tile", [8, 128], indirect=True)
def test_no_token_is_dropped_under_a_skewed_router(rng, tile):
    """One held expert is every token's first pick, eight times what an
    even router sends: more tiles run, or one tile fills — and every token
    still gets that expert's part."""
    layer = _layer(rng)
    layer["router"][:, 5] += 3.0 * np.sign(layer["router"][:, 5])
    x = np.abs(rng.normal(size=(N, D))).astype(np.float32) \
        * np.sign(layer["router"][:, 5])
    census = moe.load_census(x @ layer["router"], 4, 4, top_k=TOP_K)
    assert census["tokens_per_expert"][1] == N
    assert census["tiles"] >= -(-N // tile)
    got = _run(layer, x, 4, 4)
    np.testing.assert_allclose(got, _oracle(layer, x, 4, 4), rtol=2e-4,
                               atol=4e-5)
    assert np.all(np.abs(got).sum(-1) > 0)


def test_experts_nobody_picks_cost_no_tile_and_add_nothing(rng):
    """A router that never picks the held experts: no tile runs, the part
    is zero and so is every gradient, none of them NaN."""
    layer = _layer(rng)
    layer["router"][:, 8:12] = -10.0
    x = np.abs(rng.normal(size=(N, D))).astype(np.float32)
    assert moe.load_census(x @ layer["router"], 8, 4,
                           top_k=TOP_K)["tiles"] == 0
    share = _share(layer, 8, 4)
    out, grads = jax.value_and_grad(lambda x, share: jnp.sum(
        moe.routed_experts(x, jnp.asarray(layer["router"]), share,
                           top_k=TOP_K, first_expert=8)), argnums=(0, 1))(
        jnp.asarray(x), share)
    assert float(out) == 0.0
    for leaf in jax.tree_util.tree_leaves(grads):
        assert not np.asarray(leaf).any()


@pytest.mark.parametrize("tile", [4, 16, 128], indirect=True)
def test_gradients_match_a_dense_formulation(rng, tile):
    """The backward pass is written out tile by tile (the loop's length is
    the device's): against XLA's own gradient of a dense formulation, with
    tiles that split an expert's rows, fill exactly, and hold them all."""
    layer = _layer(rng, experts=8)
    x = jnp.asarray(rng.normal(size=(40, D)).astype(np.float32))
    params = {k: jnp.asarray(v) for k, v in layer.items()}

    def dense(params, x):
        p = jax.nn.softmax(x @ params["router"], axis=-1)
        w, idx = jax.lax.top_k(p, TOP_K)
        w = w / w.sum(-1, keepdims=True)
        gates = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None],
                                     idx].set(w)
        out = 0.0
        for e in range(2, 6):
            h = jax.nn.silu(x @ params["gate_proj"][e]) \
                * (x @ params["up_proj"][e])
            out = out + gates[:, e:e + 1] * (h @ params["down_proj"][e])
        return jnp.sum(jnp.sin(out))

    def routed(params, x):
        share = {k: params[k][2:6] for k in ("gate_proj", "up_proj",
                                             "down_proj")}
        return jnp.sum(jnp.sin(moe.routed_experts(
            x, params["router"], share, top_k=TOP_K, first_expert=2)))

    want = jax.grad(dense, argnums=(0, 1))(params, x)
    got = jax.grad(routed, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("tile", [1, 5, 32, 128], indirect=True)
def test_the_tile_does_not_change_the_result(rng, tile):
    layer = _layer(rng)
    x = rng.normal(size=(N, D)).astype(np.float32)
    np.testing.assert_allclose(_run(layer, x, 12, 4),
                               _oracle(layer, x, 12, 4), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("tile", [8], indirect=True)
def test_under_jit_and_remat_the_gradient_is_the_same(rng, tile):
    """As the model runs it: compiled, inside ``jax.checkpoint``."""
    layer = _layer(rng)
    x = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    share = _share(layer, 12, 4)

    def loss(x, share):
        return jnp.sum(jnp.sin(moe.routed_experts(
            x, jnp.asarray(layer["router"]), share, top_k=TOP_K,
            first_expert=12)))

    want = jax.grad(loss, argnums=(0, 1))(x, share)
    got = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1)))(x, share)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tile", [2], indirect=True)
def test_load_census_against_a_hand_count(tile):
    logits = np.array([[4., 3., 2., 1., 0., -1.],
                       [0., 1., 2., 3., 4., 5.],
                       [9., 0., 8., 0., 7., 0.],
                       [1., 1., 1., 1., 1., 1.]])   # ties: lowest index
    # top-2 picks: {0,1}, {5,4}, {0,2}, {0,1}
    census = moe.load_census(logits, 0, 3, top_k=2)
    assert census == {"tokens_per_expert": [3, 2, 1], "assignments": 6,
                      "largest_over_mean": 1.5, "tiles": 4}
    census = moe.load_census(logits, 3, 3, top_k=2)
    assert census["tokens_per_expert"] == [0, 1, 1]
    assert moe.load_census(logits, 3, 1, top_k=2)["largest_over_mean"] == 0.0


def _dense_groups(params, x, *, capacity):
    """The held experts' part (experts 2 to 5 of 8) of every group of ``x``
    ``[groups, n, d]`` as dense algebra that XLA differentiates itself: a
    group is routed on its own, and with a ``capacity`` an expert keeps
    its first ``capacity`` picks of a group in row order."""
    def one_group(x):
        p = jax.nn.softmax(x @ params["router"], axis=-1)
        w, idx = jax.lax.top_k(p, TOP_K)
        w = w / w.sum(-1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        gates = jnp.zeros_like(p).at[rows, idx].set(w)
        picked = jnp.zeros(p.shape, bool).at[rows, idx].set(True)
        if capacity is not None:
            gates = jnp.where(jnp.cumsum(picked, axis=0) <= capacity, gates,
                              0.0)
        out = 0.0
        for e in range(2, 6):
            h = jax.nn.silu(x @ params["gate_proj"][e]) \
                * (x @ params["up_proj"][e])
            out = out + gates[:, e:e + 1] * (h @ params["down_proj"][e])
        return out

    return jnp.stack([one_group(g) for g in x])


def _grouped(params, x, *, capacity_factor):
    groups, n, _ = x.shape
    share = {k: params[k][2:6] for k in ("gate_proj", "up_proj", "down_proj")}
    return moe.grouped_routed_experts(
        x.reshape(1, groups * n, D), params["router"], share, top_k=TOP_K,
        first_expert=2, group_rows=n,
        capacity_factor=capacity_factor).reshape(x.shape)


class _Grouped(nn.Module):
    """The grouped call as a model makes it, its matrices the module's."""
    capacity_factor: float = None

    @nn.compact
    def __call__(self, x):
        params = {k: self.get_variable("params", k)
                  for k in ("router", "gate_proj", "up_proj", "down_proj")}
        return _grouped(params, x, capacity_factor=self.capacity_factor)


GROUP_ROWS, HELD_OF_8 = 24, 4


@pytest.mark.parametrize("tile", [4], indirect=True)
@pytest.mark.parametrize("capacity_factor", [None, 0.75])
@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("how", ["eager", "jit_remat"])
def test_groups_in_one_call_match_the_dense_oracle_a_group(
        rng, tile, groups, capacity_factor, how):
    """One call over 1, 2 and 4 groups of rows: the output and the
    gradients to ``x``, the router and the experts' three matrices are the
    dense oracle's, whose gradients to the shared leaves are the sums over
    the groups — with an expert's load unbounded and bounded (9 picks a
    group, so the fullest experts drop rows), and as a model runs it:
    compiled, the layer under ``nn.remat``."""
    layer = _layer(rng, experts=8)
    params = {k: jnp.asarray(v) for k, v in layer.items()}
    x = jnp.asarray(rng.normal(size=(groups, GROUP_ROWS, D)).astype(
        np.float32))
    capacity = None if capacity_factor is None else math.ceil(
        capacity_factor * GROUP_ROWS * TOP_K / 8)

    def loss(part):
        return lambda params, x: jnp.sum(jnp.sin(part(params, x)))

    want_out = _dense_groups(params, x, capacity=capacity)
    want = jax.grad(loss(functools.partial(
        _dense_groups, capacity=capacity)), argnums=(0, 1))(params, x)
    if how == "eager":
        part = functools.partial(_grouped, capacity_factor=capacity_factor)
        got_out = part(params, x)
        got = jax.grad(loss(part), argnums=(0, 1))(params, x)
    else:
        module = nn.remat(_Grouped)(capacity_factor=capacity_factor)

        def part(params, x):
            return module.apply({"params": params}, x)

        got_out = jax.jit(part)(params, x)
        got = jax.jit(jax.grad(loss(part), argnums=(0, 1)))(params, x)
    if capacity is not None:
        # the bound binds: some held pick is dropped in some group
        free = _dense_groups(params, x, capacity=None)
        assert np.abs(np.asarray(free - want_out)).max() > 1e-3
    np.testing.assert_allclose(got_out, want_out, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def _eqns(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, each with
    the primitives of the equations it sits in, outermost first."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn,))


@pytest.mark.parametrize("tile", [4], indirect=True)
def test_the_backward_pass_carries_one_accumulator_through_the_groups(
        rng, tile):
    """The gradient of a four-group call, as traced: the three float32
    accumulators of the experts' shapes are made once, outside the loop
    over the groups, and that loop carries them; nothing of those shapes
    is added inside it (a tile adds one expert's slice, in place), where a
    loop whose groups each made their own would sum them a group
    (``add_any``)."""
    groups, held = 4, HELD_OF_8
    layer = _layer(rng, experts=8)
    params = {k: jnp.asarray(v) for k, v in layer.items()}
    x = jnp.asarray(rng.normal(size=(groups, GROUP_ROWS, D)).astype(
        np.float32))
    shapes = {(held, D, F), (held, F, D)}

    def loss(params, x):
        return jnp.sum(jnp.sin(_grouped(params, x, capacity_factor=1.25)))

    def of_the_experts(eqn):
        return [v for v in eqn.outvars if v.aval.shape in shapes
                and v.aval.dtype == jnp.float32]

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr
    carrying = [eqn for eqn, _ in _eqns(jaxpr)
                if eqn.primitive.name == "scan"
                and eqn.params["length"] == groups
                and len(of_the_experts(eqn)) == 3]
    assert len(carrying) == 1, "one loop over the groups carries the three"
    zeroed, summed = [], []
    for eqn, inside in _eqns(jaxpr):
        if not of_the_experts(eqn):
            continue
        in_the_loop = any(outer is carrying[0] for outer in inside)
        if eqn.primitive.name == "broadcast_in_dim":
            zeroed.append(in_the_loop)
        if eqn.primitive.name in ("add_any", "add"):
            summed.append(in_the_loop)
    assert zeroed == [False] * 3
    assert not any(summed)


def test_one_group_is_a_call_and_no_loop(rng):
    """``[n, d]`` rows are one group: the traced gradient holds the two
    tile loops and no loop over groups, the program the layer was before it
    took groups (``models/qwen3_next.py`` calls it so)."""
    layer = _layer(rng)
    x = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    share = _share(layer, 12, 4)

    def loss(x, share):
        return jnp.sum(jnp.sin(moe.routed_experts(
            x, jnp.asarray(layer["router"]), share, top_k=TOP_K,
            first_expert=12)))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, share).jaxpr
    loops = [eqn.primitive.name for eqn, _ in _eqns(jaxpr)
             if eqn.primitive.name in ("scan", "while")]
    assert loops == ["while", "while"]


def test_traced_layers_are_counted(monkeypatch, rng):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    layer = _layer(rng)
    x = rng.normal(size=(N, D)).astype(np.float32)
    before = _count(metrics, 1), _count(metrics, 3)
    _run(layer, x, 8, 4)
    assert (_count(metrics, 1), _count(metrics, 3)) == (before[0] + 1,
                                                        before[1])
    moe.grouped_routed_experts(
        jnp.asarray(x)[None], jnp.asarray(layer["router"]),
        _share(layer, 8, 4), top_k=TOP_K, first_expert=8, group_rows=N // 3)
    assert (_count(metrics, 1), _count(metrics, 3)) == (before[0] + 1,
                                                        before[1] + 1)


def _count(metrics, groups):
    for s in metrics.registry.snapshot()["metrics"].get(
            "hvd_moe_layers_traced_total", {}).get("samples", []):
        if s["labels"] == {"held": "4", "top_k": str(TOP_K),
                           "rule": "route_top_k", "groups": str(groups)}:
            return s["value"]
    return 0
