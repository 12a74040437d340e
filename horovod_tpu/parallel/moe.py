"""Expert parallelism: mixture-of-experts with all-to-all token dispatch.

The reference implements data parallelism only (SURVEY §2.6: EP
"absent") — this is the last letter of the TPU build's parallelism layer
(dp / tp / sp / pp / ep), in the GShard/Mesh-TensorFlow formulation that
XLA compiles well: static capacity-bounded dispatch tensors (no
data-dependent shapes), einsum dispatch/combine, and ONE ``all_to_all``
each way over the ``ep`` mesh axis to move token buffers between the
ranks that hold the tokens and the ranks that hold the experts.

Layout (inside a shard_map over ``axis``): each rank holds ``n_local``
tokens and ``experts_per_rank`` experts; E = ep_size *
experts_per_rank.  Top-1 routing with per-expert capacity C — tokens
beyond capacity are dropped (standard GShard semantics; size C
generously for tests).

Verification: the dispatch/combine ``all_to_all`` pair is modelled by
the schedule checker under the ``axis:<ep>`` group; the untiled
split-axis-0 contract (leading dispatch dimension == ep axis size) is
HVD015's axis-shape check — a literal capacity reshape that contradicts
a literal mesh declaration is flagged statically.  This module's
dispatch tensors are shaped by the symbolic axis size, so the contract
holds by construction.

:func:`routed_experts` is the layer for models with many small experts
and several a token (top-k of hundreds): it is told which experts it
holds, routes over the router's full width by the rule it is given
(:func:`route_top_k`, softmax, or :func:`route_sigmoid_top_k`), drops nothing unless the
caller bounds an expert's load, and computes its own experts' part of the result as a grouped matrix product over the
assignments sorted by expert, a tile of rows at a time, as many tiles as
the router sent rows.  :func:`load_census` is its model on the host: how
many tokens each held expert gets, and how many tiles that makes.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import metrics

# The expert layer's device scopes (docs/profiling.md): routing and data
# movement, and the grouped products.
ROUTE_SCOPE = "hvd_moe_route"
EXPERTS_SCOPE = "hvd_moe_experts"
# What the routing's backward pass and the held part read, by
# ``checkpoint_name`` (an identity outside a checkpoint): the router's
# float32 logits over its full width, a token's picks and their scores, the
# sorted order and the held experts' sizes.  A recomputed layer whose policy
# saves the name (``models/recompute.py``) runs neither the router's
# product, the top-k nor the sort again; under a loop over groups the kept
# arrays are the loop's stacked outputs.
ROUTING = "hvd_moe_routing"


def top1_dispatch(gates: jnp.ndarray, capacity: int):
    """Build static dispatch/combine tensors from router probabilities.

    Args:
      gates: ``[n, E]`` router probabilities (softmax output).
      capacity: per-expert buffer size C.

    Returns ``(dispatch [n, E, C] bool-ish f32, combine [n, E, C] f32)``:
    token t goes to slot ``position(t)`` of its argmax expert unless the
    expert is over capacity; combine carries the gate probability.
    """
    n, e = gates.shape
    expert = jnp.argmax(gates, axis=-1)                     # [n]
    # Buffer positions are computed in int32: a low-precision cumsum
    # (e.g. bf16 gates) saturates at 256 tokens and collides slots.
    onehot_i = jax.nn.one_hot(expert, e, dtype=jnp.int32)   # [n, E]
    pos = (jnp.cumsum(onehot_i, axis=0) - onehot_i) * onehot_i  # [n, E]
    pos = jnp.sum(pos, axis=-1)                             # [n] int32
    keep = pos < capacity
    onehot = onehot_i.astype(gates.dtype)                   # [n, E]
    gate = jnp.max(gates * onehot, axis=-1) * keep          # [n]
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)  # [n, C]
    dispatch = onehot[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def moe_apply(expert_fn: Callable, expert_params, x, router_kernel, *,
              capacity: int, axis: str = "ep"):
    """One EP MoE layer inside a shard_map over ``axis``.

    Args:
      expert_fn: ``(params_for_one_expert, tokens [m, d]) -> [m, d]``.
      expert_params: THIS rank's experts, stacked ``[experts_per_rank,
        ...]`` (vmapped over).
      x: this rank's tokens ``[n_local, d]``.
      router_kernel: ``[d, E]`` routing weights (replicated; E = ep *
        experts_per_rank).
      capacity: per-expert, per-source-rank buffer size.

    Returns ``[n_local, d]`` with each token's expert output weighted by
    its gate (dropped tokens contribute zero, as in GShard top-1).
    """
    ep = lax.axis_size(axis)
    _, d = x.shape
    e = router_kernel.shape[-1]
    if e % ep:
        raise ValueError(f"experts {e} not divisible by ep={ep}")
    per_rank = e // ep

    gates = jax.nn.softmax(
        (x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)), axis=-1
    ).astype(x.dtype)
    dispatch, combine = top1_dispatch(gates, capacity)

    # gather token buffers per expert: [E, C, d]
    expert_in = jnp.einsum("nd,nec->ecd", x, dispatch)
    # reshape to [ep, per_rank, C, d] and all_to_all the ep dim: after
    # the exchange this rank holds, for ITS experts, every source rank's
    # buffers: [ep(src), per_rank, C, d]
    expert_in = expert_in.reshape(ep, per_rank, capacity, d)
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                               concat_axis=0, tiled=False)
    # run this rank's experts on [src*ep buffers x C] tokens each
    flat = jnp.moveaxis(expert_in, 1, 0).reshape(
        per_rank, ep * capacity, d
    )
    out = jax.vmap(expert_fn)(expert_params, flat)     # [per_rank, ep*C, d]
    out = jnp.moveaxis(
        out.reshape(per_rank, ep, capacity, d), 0, 1
    )                                                  # [ep, per_rank, C, d]
    # route back: inverse all_to_all
    out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(e, capacity, d)
    # combine on the token side
    return jnp.einsum("ecd,nec->nd", out, combine.astype(out.dtype))


# ---------------------------------------------------------------------------
# top-k routing over held experts, nothing dropped
# ---------------------------------------------------------------------------


def _logits(x, router_kernel):
    """The router's outputs ``[n, E]`` float32, the product at ``highest``
    precision (on a TPU it is otherwise one bfloat16 pass and flips picks);
    named (:data:`ROUTING`): a rule's scores are elementwise in them."""
    return checkpoint_name(
        jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), ROUTING)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(scores, top_k: int):
    """``lax.top_k`` over the last axis, ``(values, picks)``, whose backward
    pass reads the *named* picks (:data:`ROUTING`, with the values).
    ``lax.top_k``'s own derivative reads the picks of the very call it
    differentiates, which no name reaches: a checkpoint that saved every
    name would still run the top-k a second time to have them."""
    return tuple(lax.top_k(scores, top_k))


def _top_k_fwd(scores, top_k):
    values, picks = (checkpoint_name(out, ROUTING)
                     for out in lax.top_k(scores, top_k))
    return (values, picks), (picks, scores)


def _top_k_bwd(top_k, kept, cotangents):
    """A pick's cotangent added into its place in a row of zeros: what
    ``lax.top_k``'s own derivative transposes to (``[n, E]`` scores)."""
    picks, scores = kept        # of the scores only the shape is read
    return (lax.scatter_add(
        jnp.zeros_like(scores), picks[..., None], cotangents[0],
        lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(1,),
            scatter_dims_to_operand_dims=(1,), operand_batching_dims=(0,),
            scatter_indices_batching_dims=(0,)),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def route_top_k(x, router_kernel, top_k: int):
    """``(weights [n, top_k] float32, experts [n, top_k] int32)``: softmax
    over the router's full width in float32, the ``top_k`` largest, their
    weights divided by their sum."""
    weights, experts = _top_k(
        jax.nn.softmax(_logits(x, router_kernel), axis=-1), top_k)
    return weights / jnp.sum(weights, axis=-1, keepdims=True), experts


def route_sigmoid_top_k(x, router_kernel, top_k: int, *, bias,
                        scale: float = 1.0, eps: float = 1e-20):
    """The same pair by the other published rule: each output's score is
    its own sigmoid (float32), the picks are the ``top_k`` largest of
    ``score + bias``, and a pick's weight is its score *without* the bias,
    divided by the picks' sum plus ``eps`` (1e-20 in ``deepseek_v3`` and
    ``nemotron_h``, 1e-6 in ``lfm2_moe``) and multiplied by ``scale``.
    ``bias`` (``[E]``) moves which experts a token picks and never what it
    weighs them by: the selection bias an auxiliary-loss-free balancer
    steers, a buffer and not a parameter (no gradient reaches it).  A
    caller binds ``bias``, ``scale`` and ``eps`` (``functools.partial``)
    and passes the rule as ``route``."""
    scores = jax.nn.sigmoid(_logits(x, router_kernel))
    # no derivative is taken through this top-k: its values are not used
    experts = checkpoint_name(
        lax.top_k(scores + jnp.asarray(bias, jnp.float32), top_k)[1],
        ROUTING)
    weights = checkpoint_name(
        jnp.take_along_axis(scores, experts, axis=-1), ROUTING)
    total = jnp.sum(weights, axis=-1, keepdims=True) + eps
    return weights / total * scale, experts


def routing_bytes(rows: int, experts: int, top_k: int) -> int:
    """Bytes of what :data:`ROUTING` names over ``rows`` tokens: float32
    logits over the router's ``experts`` outputs, and a pick, its score and
    a place in the order for each of a token's ``top_k`` assignments (the
    held experts' sizes, a few integers a group, are not counted)."""
    return 4 * rows * (experts + 3 * top_k)


#: Rows of one grouped product.  A held expert's assignments are taken
#: ``TILE`` at a time, so what the layer costs follows the rows the router
#: sends here a tile at a time (an expert with 257 rows costs two tiles,
#: with none it costs nothing).  On a v5e at 2048 x 512 experts a tile of
#: 256 costs 0.26 ms forward and backward and one of 128 0.19 ms: 256 is
#: the cheaper for every load over 128 rows an expert (PERF.md, PR 26).
TILE = 256


def _tile_schedule(sizes, tile: int):
    """``(tiles [held], last [held], offsets [held])``: how many tiles each
    held expert's rows fill, the index one past each expert's last tile
    (so ``last[-1]`` is the number of tiles this step), and where each
    expert's rows start among the sorted assignments."""
    tiles = -(-sizes // tile)
    return tiles, jnp.cumsum(tiles), jnp.cumsum(sizes) - sizes


def _tile_rows(j, order, sizes, schedule, *, tile: int, top_k: int, n: int):
    """Tile ``j``: ``(expert, picked [tile], token [tile])``.  ``picked``
    indexes the flat assignments (``[n * top_k]``), ``token`` the rows of
    ``x``.  A slot past the expert's last row gets indices out of bounds,
    each its own, so a gather fills it with zeros and a scatter drops it
    while the indices stay unique and ascending (the sort is stable: an
    expert's rows are in token order)."""
    tiles, last, offsets = schedule
    e = jnp.sum(last <= j)
    slot = jnp.arange(tile, dtype=jnp.int32)
    row = (j - (last[e] - tiles[e])) * tile + slot
    valid = row < sizes[e]
    picked = order[jnp.where(valid, offsets[e] + row, 0)]
    return (e, jnp.where(valid, picked, n * top_k + slot),
            jnp.where(valid, picked // top_k, n + slot))


def _dot(dtype):
    """A product that accumulates in float32; exact for float32 operands
    (a TPU's default there is one bfloat16 pass)."""
    exact = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return functools.partial(jnp.dot, precision=exact,
                             preferred_element_type=jnp.float32)


#: An expert's form: ``{name: its matrices, in the order the tile loops
#: take them}``, the last one back to the model's width.  ``swiglu``:
#: ``down(silu(gate x) * up x)``; ``relu2``: ``down(relu(up x) ** 2)``, no
#: gate.  A caller names the form (``routed_experts(form=...)``); routing,
#: the sort, the tiles, capacity and groups do not depend on it.
FORMS = {"swiglu": ("gate_proj", "up_proj", "down_proj"),
         "relu2": ("up_proj", "down_proj")}


def _expert_forward(xs, params, e, form):
    """Expert ``e`` on the rows ``xs``: its matrices (``FORMS[form]``) in
    ``xs``'s dtype, and ``(pre, hidden, y)``: what the rows give under the
    matrices before ``down`` (``swiglu``: ``(xs gate, xs up)``; ``relu2``:
    ``(xs up,)``) and ``y = hidden down`` in float32, ``hidden`` (``silu(a)
    * b``; ``relu(a) ** 2``) in ``xs``'s dtype."""
    dtype, dot = xs.dtype, _dot(xs.dtype)
    mats = tuple(
        lax.dynamic_index_in_dim(params[k], e, keepdims=False).astype(dtype)
        for k in FORMS[form])
    pre = tuple(dot(xs, m) for m in mats[:-1])
    if form == "relu2":
        hidden = jnp.square(jax.nn.relu(pre[0])).astype(dtype)
    else:
        hidden = (jax.nn.silu(pre[0]) * pre[1]).astype(dtype)
    return mats, (pre, hidden, dot(hidden, mats[-1]))


def _expert_backward(xs, mats, pre, hidden, dy, form):
    """``(dxs, {name: gradient})`` in float32 from ``dy``, the weighted
    gradient to the expert's output in ``xs``'s dtype: through ``down``,
    the form's elementwise part and the matrices before it."""
    dtype, dot = xs.dtype, _dot(xs.dtype)
    names = FORMS[form]
    dhidden = dot(dy, mats[-1].T)
    if form == "relu2":
        dpre = ((dhidden * 2.0 * jax.nn.relu(pre[0])).astype(dtype),)
    else:
        a, b = pre
        sig = jax.nn.sigmoid(a)
        dpre = ((dhidden * b * sig * (1.0 + a * (1.0 - sig))).astype(dtype),
                (dhidden * a * sig).astype(dtype))
    dxs = dot(dpre[0], mats[0].T)
    for d, m in zip(dpre[1:], mats[1:]):
        dxs = dxs + dot(d, m.T)
    grads = {k: dot(xs.T, d) for k, d in zip(names, dpre)}
    grads[names[-1]] = dot(hidden.T, dy)
    return dxs, grads


def _each_group(one_group, carry, rows):
    """``lax.scan`` of ``one_group`` over the groups of ``rows``, arrays
    whose first is ``x``: ``[groups, n, d]``, or ``[n, d]`` for a caller
    with one group, which is a call and no loop, so that caller compiles
    to what it would if there were no groups."""
    if rows[0].ndim == 2:
        return one_group(carry, rows)
    return lax.scan(one_group, carry, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_part(x, weights, params, order, sizes, tile, top_k, form):
    """The held experts' part of the layer, group by group: a loop over a
    group's tiles (a number the device decides), each one expert's next
    ``tile`` rows — gathered from the group's ``x``, through the expert
    (of ``form``, :data:`FORMS`), weighted, added to their tokens in the
    group's own float32 ``[n, d]``.
    ``x``: ``[groups, n, d]``; ``weights``: ``[groups, n * top_k]``
    float32, one an assignment; ``order``: a group's assignments sorted by
    held expert, held ones first; ``sizes``: ``[groups, held]``, rows of
    each held expert (one group: no leading axis on any of the four).  A
    tile loop's length is not known when the step is traced, so XLA cannot
    transpose it: the backward pass is the same loops written out
    (:func:`_held_part_bwd`), and it carries one float32 accumulator of
    each of the experts' matrices through all the groups."""
    n, d = x.shape[-2:]

    def one_group(_, group):
        x, weights, order, sizes = group
        schedule = _tile_schedule(sizes, tile)

        def one_tile(j, out):
            with jax.named_scope(ROUTE_SCOPE):
                e, picked, token = _tile_rows(j, order, sizes, schedule,
                                              tile=tile, top_k=top_k, n=n)
                xs = x.at[token].get(mode="fill", fill_value=0)
                w = weights.at[picked].get(mode="fill", fill_value=0)
            with jax.named_scope(EXPERTS_SCOPE):
                y = _expert_forward(xs, params, e, form)[1][2]
            with jax.named_scope(ROUTE_SCOPE):
                return out.at[token].add(y * w[:, None], mode="drop",
                                         unique_indices=True,
                                         indices_are_sorted=True)

        out = lax.fori_loop(0, schedule[1][-1], one_tile,
                            jnp.zeros((n, d), jnp.float32))
        return None, out.astype(x.dtype)

    return _each_group(one_group, None, (x, weights, order, sizes))[1]


def _held_part_fwd(x, weights, params, order, sizes, tile, top_k, form):
    return (_held_part(x, weights, params, order, sizes, tile, top_k, form),
            (x, weights, params, order, sizes))


def _held_part_bwd(tile, top_k, form, kept, dout):
    """Group by group and tile by tile again, each tile recomputed from the
    layer's inputs (nothing a tile made is kept).  The gradients to the
    experts' matrices accumulate in float32 in one ``[held, ...]`` array a
    matrix of the form (three, or ``relu2``'s two) that are zeroed once a call and carried through every group's tile
    loop, an expert's sum in place in its slice; a group's gradient to its
    ``x`` accumulates in float32 in that group's own ``[n, d]``."""
    x, weights, params, order, sizes = kept
    n, d = x.shape[-2:]
    dtype = x.dtype
    scatter = dict(mode="drop", unique_indices=True, indices_are_sorted=True)

    def one_group(dparams, group):
        x, weights, order, sizes, dout = group
        schedule = _tile_schedule(sizes, tile)

        def one_tile(j, carry):
            dx, dweights, dparams = carry
            with jax.named_scope(ROUTE_SCOPE):
                e, picked, token = _tile_rows(j, order, sizes, schedule,
                                              tile=tile, top_k=top_k, n=n)
                xs = x.at[token].get(mode="fill", fill_value=0)
                w = weights.at[picked].get(mode="fill", fill_value=0)
                dys = dout.at[token].get(mode="fill", fill_value=0).astype(
                    jnp.float32)
            with jax.named_scope(EXPERTS_SCOPE):
                mats, (pre, hidden, y) = _expert_forward(xs, params, e, form)
                dw = jnp.sum(dys * y, axis=-1)
                dy = (dys * w[:, None]).astype(dtype)
                dxs, grads = _expert_backward(xs, mats, pre, hidden, dy,
                                              form)
                dparams = {k: lax.dynamic_update_index_in_dim(
                    acc, lax.dynamic_index_in_dim(acc, e, keepdims=False)
                    + grads[k], e, 0) for k, acc in dparams.items()}
            with jax.named_scope(ROUTE_SCOPE):
                return (dx.at[token].add(dxs, **scatter),
                        dweights.at[picked].set(dw, **scatter), dparams)

        dx, dweights, dparams = lax.fori_loop(
            0, schedule[1][-1], one_tile,
            (jnp.zeros((n, d), jnp.float32), jnp.zeros_like(weights),
             dparams))
        return dparams, (dx.astype(dtype), dweights)

    dparams, (dx, dweights) = _each_group(
        one_group, {k: jnp.zeros(params[k].shape, jnp.float32)
                    for k in FORMS[form]},
        (x, weights, order, sizes, dout))
    return (dx, dweights,
            {k: dparams[k].astype(params[k].dtype) for k in dparams},
            None, None)


_held_part.defvjp(_held_part_fwd, _held_part_bwd)


def routed_experts(x, router_kernel, expert_params, *, top_k: int,
                   first_expert: int = 0, capacity: Optional[int] = None,
                   route: Callable = route_top_k, form: str = "swiglu"):
    """The part of a top-k mixture-of-experts layer that the experts held
    here give: ``sum_j w_j * down_j(silu(gate_j x) * up_j x)`` (``form``
    ``"swiglu"``; ``"relu2"``: ``down_j(relu(up_j x) ** 2)``) over those
    of a token's ``top_k`` picks that fall on ``[first_expert,
    first_expert + held)``.

    The router keeps its full width and its ``top_k``, and the weights are
    normalised over all the picks, not over the ones held: the parts of
    all the shares add up to the whole layer.  Static shapes and, unless
    ``capacity`` bounds an expert, no dropped token: the assignments are
    sorted by expert into one order (``n * top_k`` indices, the held
    experts' first), and a loop whose length the device decides takes each
    held expert's rows ``TILE`` at a time through that expert's
    matrices — a grouped matrix product at the granularity of a tile.  Only an expert's last tile is
    padded, so the layer's cost follows the number of rows the router
    sends here, smoothly, from none to every token on one expert; there is
    no tail to handle, because rows past the held assignments are never
    visited.  One chip's layer: there is no exchange here and nothing in
    its place; an ``ep`` exchange like :func:`moe_apply`'s goes round this
    function (tokens in, their parts out), not inside it.

    ``x`` may hold several groups of rows (``[groups, n, d]``): each group
    is routed, bounded, sorted and tiled on its own, as a call of its own
    would be, and the backward pass carries one float32 accumulator of each
    of the experts' matrices through all the groups (zeroed once and cast
    once a call, a tile's products added in place), so what a group adds
    to the experts' gradients is never summed with another group's as
    whole arrays.  ``[n, d]`` is one group.

    Args:
      x: ``[n, d]`` tokens, or ``[groups, n, d]``.
      router_kernel: ``[d, E]``, all ``E`` experts of the layer.
      expert_params: ``{"gate_proj": [held, d, f], "up_proj": [held, d, f],
        "down_proj": [held, f, d]}``, the experts held here (``relu2``: no
        ``gate_proj``).
      top_k: experts a token.
      first_expert: index of the first held expert.
      capacity: the most of a group's ``n`` tokens an expert takes,
        GShard's bound on a group: an expert's assignments past its first
        ``capacity`` in token order are dropped, their weights with them
        (a token's other picks keep theirs).  ``None``: no bound.
      route: how a token's picks and their weights are made, ``(x,
        router_kernel, top_k) -> (weights [n, top_k] float32, experts [n,
        top_k] int32)`` over the router's full width: :func:`route_top_k`
        (softmax) or :func:`route_sigmoid_top_k` with its bias and scale
        bound.  Held experts, the sort, the tiles and ``capacity`` do not
        depend on it.
      form: an expert's form, a key of :data:`FORMS`: ``"swiglu"`` (the
        gated SiLU pair, three matrices) or ``"relu2"`` (two matrices,
        ``hidden = relu(up x) ** 2``, no gate).  Schedule, gathers,
        scatter, capacity, groups and the float32 accumulators (one a
        matrix) are the same loops for both.

    Returns ``x``'s shape in ``x``'s dtype.
    """
    if form not in FORMS:
        raise ValueError(f"an expert's form is one of {sorted(FORMS)}, "
                         f"not {form!r}")
    held = expert_params[FORMS[form][-1]].shape[0]
    rule = getattr(route, "func", route).__name__
    metrics.record_moe_layer(
        held, top_k, rule if form == "swiglu" else f"{rule}+{form}",
        1 if x.ndim == 2 else x.shape[0])

    def route_group(_, rows):
        x, = rows
        with jax.named_scope(ROUTE_SCOPE):
            weights, experts = route(x, router_kernel, top_k)
            local = experts - first_expert
            local = jnp.where((local >= 0) & (local < held), local,
                              held).reshape(-1).astype(jnp.int32)
            if capacity is not None:
                # an assignment's place among its expert's, in token order;
                # one past the capacity counts as an expert's that lives
                # elsewhere
                mine = local[:, None] == jnp.arange(held)[None, :]
                place = jnp.sum(
                    jnp.where(mine, jnp.cumsum(mine, axis=0,
                                               dtype=jnp.int32), 0), axis=1)
                local = jnp.where(place > capacity, held, local)
            sizes = jnp.sum(local[:, None] == jnp.arange(held)[None, :],
                            axis=0, dtype=jnp.int32)
            # stable: the held assignments first, expert by expert, each
            # expert's in token order
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
        return None, (weights.reshape(-1), checkpoint_name(order, ROUTING),
                      checkpoint_name(sizes, ROUTING))

    _, (weights, order, sizes) = _each_group(route_group, None, (x,))
    params = {k: expert_params[k] for k in FORMS[form]}
    return _held_part(x, weights, params, order, sizes, TILE, top_k, form)


def grouped_routed_experts(x, router_kernel, expert_params, *, top_k: int,
                           first_expert: int = 0,
                           group_rows: Optional[int] = None,
                           capacity_factor: Optional[float] = None,
                           route: Callable = route_top_k,
                           form: str = "swiglu"):
    """:func:`routed_experts` over the rows of ``x`` ``[b, rows, d]`` in
    groups, GShard's way of bounding an expert's load: the ``b * rows``
    rows, in order, form groups of ``group_rows`` (one group when ``None``
    or when there are fewer rows), each routed on its own, and with a
    ``capacity_factor`` an expert takes at most ``ceil(capacity_factor *
    group * top_k / E)`` rows of a group, the first in row order.  One
    call for all the groups, so the backward pass carries the experts'
    float32 gradient accumulators from group to group.  Returns ``[b,
    rows, d]``."""
    b, rows, d = x.shape
    n = b * rows
    group = min(group_rows or n, n)
    if n % group:
        raise ValueError(f"{n} rows are not whole groups of {group}")
    capacity = None if capacity_factor is None else math.ceil(
        capacity_factor * group * top_k / router_kernel.shape[-1])
    return routed_experts(
        x.reshape(n // group, group, d), router_kernel, expert_params,
        top_k=top_k, first_expert=first_expert, capacity=capacity,
        route=route, form=form).reshape(b, rows, d)


def load_census(router_logits, first_expert: int, held: int, *,
                top_k: int) -> dict:
    """The expert layer's model on the host, beside the flash kernels'
    ``tile_census``: from router logits ``[n, E]``, how many tokens each
    of the experts ``[first_expert, first_expert + held)`` gets under
    top-``top_k`` routing, how many assignments that is in all, the
    largest load over the mean (1.0 is an even router), and how many tiles
    of ``TILE`` rows :func:`routed_experts` runs for them: the layer's cost
    is so many products."""
    logits = np.asarray(router_logits, np.float64)
    picks = np.argsort(-logits, axis=-1, kind="stable")[:, :top_k]
    loads = np.bincount(picks.reshape(-1),
                        minlength=logits.shape[-1])[first_expert:
                                                    first_expert + held]
    mean = loads.mean() if held else 0.0
    return {"tokens_per_expert": [int(c) for c in loads],
            "assignments": int(loads.sum()),
            "largest_over_mean": float(loads.max() / mean) if mean else 0.0,
            "tiles": int(sum(-(-int(c) // TILE) for c in loads))}
