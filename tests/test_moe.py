"""Expert parallelism: EP MoE layer vs dense oracle — routing, capacity
drops, gradients (beyond reference parity: the reference is DP-only,
SURVEY §2.6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.moe import moe_apply, top1_dispatch

D = 8
EP = 4
PER_RANK = 2           # experts per rank -> E = 8
E = EP * PER_RANK
N_LOCAL = 16           # tokens per rank


def _expert_fn(p, x):
    return jnp.tanh(x @ p["w"]) @ p["v"]


def _make_params(rng):
    experts = [
        {"w": rng.normal(size=(D, 16)).astype(np.float32) * 0.5,
         "v": rng.normal(size=(16, D)).astype(np.float32) * 0.5}
        for _ in range(E)
    ]
    router = rng.normal(size=(D, E)).astype(np.float32)
    return experts, router


def _oracle(experts, router, x, capacity):
    """Dense single-device computation with INDEPENDENT numpy routing
    (argmax + manual position count), so dispatch bugs in the module
    cannot cancel out."""
    logits = np.asarray(x) @ np.asarray(router)
    g = np.exp(logits - logits.max(-1, keepdims=True))
    gates = g / g.sum(-1, keepdims=True)
    out = np.zeros_like(np.asarray(x))
    counts = np.zeros(E, np.int64)
    for t in range(x.shape[0]):
        ei = int(np.argmax(gates[t]))
        if counts[ei] >= capacity:
            continue  # dropped
        counts[ei] += 1
        y = _expert_fn(
            {k: jnp.asarray(v) for k, v in experts[ei].items()},
            jnp.asarray(x[t][None]),
        )
        out[t] = np.asarray(y)[0] * gates[t, ei]
    return out


def test_top1_dispatch_capacity():
    gates = jnp.asarray([
        [0.9, 0.1], [0.8, 0.2], [0.7, 0.3], [0.2, 0.8],
    ])
    dispatch, combine = top1_dispatch(gates, capacity=2)
    # tokens 0,1 -> expert 0 slots 0,1; token 2 dropped (over capacity);
    # token 3 -> expert 1 slot 0
    assert float(dispatch[0, 0, 0]) == 1.0
    assert float(dispatch[1, 0, 1]) == 1.0
    assert float(jnp.sum(dispatch[2])) == 0.0
    assert float(dispatch[3, 1, 0]) == 1.0
    np.testing.assert_allclose(float(combine[1, 0, 1]), 0.8, rtol=1e-6)


def test_top1_dispatch_bf16_many_tokens():
    """Regression: buffer positions must be computed in int32 — a bf16
    cumsum saturates at 256, colliding slots (tokens summed into one
    buffer entry) once an expert sees >256 tokens."""
    n = 600
    gates = jnp.full((n, 2), 0.5, dtype=jnp.bfloat16).at[:, 0].set(
        jnp.bfloat16(0.9)
    )  # every token routes to expert 0
    dispatch, _ = top1_dispatch(gates, capacity=n)
    d = np.asarray(dispatch, dtype=np.float32)
    # each kept token occupies exactly one slot...
    np.testing.assert_allclose(d.sum(axis=(1, 2)), 1.0)
    # ...and no slot holds more than one token
    assert d.sum(axis=0).max() == 1.0
    # slots 0..n-1 of expert 0 are each used exactly once
    np.testing.assert_allclose(d[:, 0, :].sum(axis=0), 1.0)


def test_moe_matches_dense_oracle(rng):
    """Per-rank EP computation == the dense oracle run on each rank's
    tokens (experts are global; each rank routes over all E)."""
    mesh = Mesh(np.array(jax.devices("cpu")[:EP]), ("ep",))
    experts, router = _make_params(rng)
    x = rng.normal(size=(EP, N_LOCAL, D)).astype(np.float32)
    capacity = N_LOCAL  # generous: no drops from capacity

    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *experts
    )  # [E, ...]

    def body(params_stack, x_local):
        # my experts: rows [rank*per_rank, (rank+1)*per_rank)
        r = jax.lax.axis_index("ep")
        mine = jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice_in_dim(a, r * PER_RANK, PER_RANK),
            params_stack,
        )
        return moe_apply(_expert_fn, mine, x_local[0],
                         jnp.asarray(router), capacity=capacity,
                         axis="ep")[None]

    from jax import lax

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("ep")), out_specs=P("ep"),
        check_vma=False,
    ))
    out = np.asarray(fn(
        jax.tree_util.tree_map(jnp.asarray, stacked),
        jax.device_put(x, NamedSharding(mesh, P("ep"))),
    ))
    with jax.default_device(jax.devices("cpu")[0]):
        for r in range(EP):
            expected = np.asarray(_oracle(experts, router, x[r], capacity))
            np.testing.assert_allclose(out[r], expected,
                                       rtol=2e-4, atol=2e-5)


def test_moe_gradients_flow(rng):
    """Router and expert gradients are finite and nonzero through the
    all_to_all round trip."""
    from jax import lax

    mesh = Mesh(np.array(jax.devices("cpu")[:EP]), ("ep",))
    experts, router = _make_params(rng)
    x = rng.normal(size=(EP, N_LOCAL, D)).astype(np.float32)
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *experts)

    def body(params_stack, router, x_local):
        r = jax.lax.axis_index("ep")

        def loss_of(args):
            ps, rt = args
            mine = jax.tree_util.tree_map(
                lambda a: lax.dynamic_slice_in_dim(
                    a, r * PER_RANK, PER_RANK), ps,
            )
            out = moe_apply(_expert_fn, mine, x_local[0], rt,
                            capacity=N_LOCAL, axis="ep")
            return jnp.sum(out ** 2)

        g_ps, g_rt = jax.grad(loss_of)((params_stack, router))
        return (jax.tree_util.tree_map(lambda a: a[None], g_ps),
                g_rt[None])

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(), P("ep")),
        out_specs=(P("ep"), P("ep")), check_vma=False,
    ))
    g_ps, g_rt = fn(
        jax.tree_util.tree_map(jnp.asarray, stacked),
        jnp.asarray(router),
        jax.device_put(x, NamedSharding(mesh, P("ep"))),
    )
    gw = np.asarray(jax.device_get(g_ps["w"]))
    grt = np.asarray(jax.device_get(g_rt))
    assert np.isfinite(gw).all() and np.isfinite(grt).all()
    assert np.abs(gw).max() > 0
    assert np.abs(grt).max() > 0


# -- the expert's form is a seam of routed_experts ----------------------------


def _dense_sum(x, router, params, form, *, top_k, first, capacity, route):
    """The held experts' part as a dense sum over experts, every expert on
    every row under a ``[rows, held]`` matrix of weights (an expert's picks
    past its first ``capacity`` in row order zeroed): plain ``jnp`` that
    JAX differentiates itself."""
    from horovod_tpu.parallel import moe

    held = params["down_proj"].shape[0]
    weights, experts = route(x, router, top_k)
    dense = jnp.zeros((x.shape[0], router.shape[1]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], experts].add(weights)
    dense = dense[:, first:first + held]
    if capacity is not None:
        dense = dense * (jnp.cumsum(dense > 0, axis=0) <= capacity)
    up = jnp.einsum("nd,edf->enf", x, params["up_proj"],
                    precision=jax.lax.Precision.HIGHEST)
    if form == "relu2":
        hidden = jnp.square(jax.nn.relu(up))
    else:
        hidden = jax.nn.silu(jnp.einsum(
            "nd,edf->enf", x, params["gate_proj"],
            precision=jax.lax.Precision.HIGHEST)) * up
    assert sorted(params) == sorted(moe.FORMS[form])
    return jnp.einsum("ne,enf,efd->nd", dense, hidden, params["down_proj"],
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("how", ["eager", "jit_remat"])
@pytest.mark.parametrize("capacity", [None, 7])
@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_an_experts_form_against_a_dense_sum_forward_and_backward(
        rng, monkeypatch, form, rule, capacity, how):
    """``form="relu2"`` is ``down(relu(up x) ** 2)`` through the loops the
    gated pair takes: the same schedule, gathers, scatter and capacity,
    two float32 accumulators where the pair has three.  Values and every
    gradient (x, the router, each of the form's matrices) against the
    dense sum, under either routing rule, with and without a capacity,
    eagerly and under ``jit`` + ``remat``."""
    import functools

    from horovod_tpu.parallel import moe

    monkeypatch.setattr(moe, "TILE", 8)
    n, d, f, experts, held, top_k, first = 96, 16, 8, 8, 4, 3, 2
    mk = lambda *s: jnp.asarray(0.4 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x, router = mk(n, d), mk(d, experts)
    params = {k: mk(held, *((f, d) if k == "down_proj" else (d, f)))
              for k in moe.FORMS[form]}
    route = moe.route_top_k if rule == "softmax" else functools.partial(
        moe.route_sigmoid_top_k, bias=jnp.zeros(experts), scale=2.5)
    weight = mk(n, d)
    kw = dict(top_k=top_k, capacity=capacity, route=route)

    def program(x, router, params):
        return jnp.sum(weight * moe.routed_experts(
            x, router, params, first_expert=first, form=form, **kw))

    def oracle(x, router, params):
        return jnp.sum(weight * _dense_sum(x, router, params, form,
                                           first=first, **kw))

    if how == "jit_remat":
        program = jax.jit(jax.checkpoint(program))
    np.testing.assert_allclose(
        np.asarray(moe.routed_experts(x, router, params, first_expert=first,
                                      form=form, **kw)),
        np.asarray(_dense_sum(x, router, params, form, first=first, **kw)),
        atol=2e-6, rtol=1e-5)
    got = jax.grad(program, argnums=(0, 1, 2))(x, router, params)
    want = jax.grad(oracle, argnums=(0, 1, 2))(x, router, params)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-6,
                                   rtol=1e-4)
    if capacity is not None:
        # the bound bites at this size: the dropless layer gives another
        free = moe.routed_experts(x, router, params, first_expert=first,
                                  form=form, top_k=top_k, route=route)
        assert float(jnp.max(jnp.abs(free - moe.routed_experts(
            x, router, params, first_expert=first, form=form, **kw)))) > 1e-4


def test_the_form_is_named_and_counted(rng, monkeypatch):
    """An unknown form is refused by name; a ``relu2`` call takes no gate
    and adds ``+relu2`` to the counter's ``rule``, a gated call's labels
    read as they did; groups go through ``grouped_routed_experts``
    alike."""
    from horovod_tpu import metrics
    from horovod_tpu.parallel import moe

    monkeypatch.setattr(metrics.registry, "enabled", True)
    monkeypatch.setattr(moe, "TILE", 8)
    n, d, f, experts, held = 32, 16, 8, 8, 4
    mk = lambda *s: jnp.asarray(0.4 * rng.normal(size=s), jnp.float32)  # noqa: E731
    x, router = mk(2, n, d), mk(d, experts)
    gated = {"gate_proj": mk(held, d, f), "up_proj": mk(held, d, f),
             "down_proj": mk(held, f, d)}
    plain = {k: gated[k] for k in ("up_proj", "down_proj")}

    def count(rule, groups):
        return sum(s["value"] for s in metrics.registry.snapshot()[
            "metrics"].get("hvd_moe_layers_traced_total", {}).get(
                "samples", [])
            if s["labels"] == {"held": str(held), "top_k": "2",
                               "rule": rule, "groups": str(groups)})

    before = count("route_top_k", 2), count("route_top_k+relu2", 2)
    with pytest.raises(ValueError, match="form"):
        moe.routed_experts(x[0], router, gated, top_k=2, form="gelu")
    got = moe.grouped_routed_experts(x, router, plain, top_k=2,
                                     group_rows=n, form="relu2")
    assert got.shape == x.shape
    assert (count("route_top_k", 2), count("route_top_k+relu2", 2)) \
        == (before[0], before[1] + 1)
    # the gate is not read: with one beside them the result is the same
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(moe.grouped_routed_experts(
            x, router, gated, top_k=2, group_rows=n, form="relu2")))
    moe.grouped_routed_experts(x, router, gated, top_k=2, group_rows=n)
    assert count("route_top_k", 2) == before[0] + 1
    assert moe.FORMS == {"swiglu": ("gate_proj", "up_proj", "down_proj"),
                         "relu2": ("up_proj", "down_proj")}


@pytest.mark.parametrize("eps", [None, 1e-20, 1e-6, 0.5])
def test_the_sigmoid_rules_eps_is_what_is_added_to_the_picks_sum(eps):
    """Scores 0.9, 0.8, 0.7 and the rest lower, top 3: the weights are
    ``scale * (0.9, 0.8, 0.7) / (2.4 + eps)``.  Unbound, ``eps`` is 1e-20
    and the rule's jaxpr is the one it was (the two accepted callers bind
    ``bias`` and ``scale`` alone); ``lfm2_moe``'s 1e-6 moves a weight by
    4e-7 of itself, and 0.5 shows that nothing else reads it."""
    from horovod_tpu.parallel import moe

    scores = np.array([0.2, 0.9, 0.1, 0.7, 0.3, 0.8, 0.6, 0.05])
    logits = np.log(scores / (1 - scores))
    x = jnp.asarray([[1.0, 0.0]], jnp.float32)
    router = jnp.asarray(np.stack([logits, np.zeros(8)]), jnp.float32)
    kw = {} if eps is None else {"eps": eps}
    w, e = moe.route_sigmoid_top_k(x, router, 3, bias=np.zeros(8),
                                   scale=2.5, **kw)
    assert np.asarray(e).tolist() == [[1, 5, 3]]
    np.testing.assert_allclose(
        np.asarray(w)[0], 2.5 * np.array([0.9, 0.8, 0.7])
        / (2.4 + (1e-20 if eps is None else eps)), rtol=1e-6)

    def text(**kw):
        return str(jax.make_jaxpr(lambda x, r: moe.route_sigmoid_top_k(
            x, r, 3, bias=np.zeros(8), scale=2.5, **kw))(x, router))

    assert (text(**kw) == text()) == (eps in (None, 1e-20))
