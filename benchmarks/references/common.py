"""What the two references share: seeded weights, plain optimizers, the
three-step training walk, per-leaf norms, and the operand rounding that
turns a reference into its lower-precision control.

Params are nested dicts of arrays; a leaf is named by its ``/``-joined
path, which is also how the program's tree is flattened, so the two line
up by name.
"""

from __future__ import annotations

import contextlib
import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: "float32" is the reference.  "fp8" is its control: every matmul and
#: convolution operand rounded to a float8 (e4m3) under a per-tensor scale,
#: the nearest precision below the bfloat16 the configurations state.
PRECISIONS = ("float32", "fp8")
#: largest finite value of a float8 with 4 exponent and 3 mantissa bits
#: that keeps IEEE's infinities, which is what ``lax.reduce_precision``
#: rounds to
_E4M3_MAX = 240.0


def operand_rounding(precision: str) -> Callable:
    """``q(x)``: identity for the reference; for the control, ``x`` rounded
    to 4 exponent and 3 mantissa bits with the largest magnitude scaled to
    the format's largest, passed straight through in the backward pass.
    ``reduce_precision`` and not a cast there and back, which XLA is free
    to drop (on the chip it does: the round trip read 0 error)."""
    if precision == "float32":
        return lambda x: x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}; "
                         f"known: {PRECISIONS}")

    def q(x):
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, _E4M3_MAX / amax, 1.0)
        rounded = jax.lax.reduce_precision(x * scale, exponent_bits=4,
                                           mantissa_bits=3) / scale
        return x + jax.lax.stop_gradient(rounded - x)

    return q


@contextlib.contextmanager
def full_precision():
    """float32 matmuls on a TPU run as one bfloat16 pass unless told
    otherwise; the reference is float32 all the way."""
    with jax.default_matmul_precision("highest"):
        yield


def flatten(tree) -> Dict[str, jnp.ndarray]:
    """Any pytree's leaves by ``/``-joined path, in the tree's own order
    (sorted, for dicts): ``{"a/b/c": leaf}``."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)

    def part(p):
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                return str(getattr(p, attr))
        return str(p)

    return {"/".join(part(p) for p in path): leaf for path, leaf in leaves}


def seeded_params(shapes: Dict[str, tuple], rule: Callable[[str, tuple],
                  tuple], seed: int):
    """Every leaf in one jitted call from the seed.  ``rule(name, shape)``
    gives ``("normal", std)``, ``("full", value)``, ``("ones",)`` or
    ``("zeros",)``.  Returns the
    flat ``{name: array}`` dict in float32."""
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            kind = rule(name, shapes[name])
            if kind[0] == "normal":
                out[name] = kind[1] * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[name], jnp.float32)
            elif kind[0] == "full":
                out[name] = jnp.full(shapes[name], kind[1], jnp.float32)
            elif kind[0] == "ones":
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = jnp.zeros(shapes[name], jnp.float32)
        return out

    # the driver's seeds pass 2**31: fold the high bits in, lose none
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(make)(key)


def unflatten(flat: Dict[str, jnp.ndarray]) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


# -- plain optimizers, as optax.adam(lr) and optax.sgd(lr, momentum) define
# them ----------------------------------------------------------------------

def adam_init(params):
    # a tree of zeros each: ``train_steps`` gives both moments' buffers to
    # the update, and a buffer can be given away once
    return {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam_update(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = state["t"] + 1
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return params, {"m": m, "v": v, "t": t}


def momentum_init(params):
    return {"trace": jax.tree_util.tree_map(jnp.zeros_like, params)}


def momentum_update(params, grads, state, *, lr, momentum=0.9):
    trace = jax.tree_util.tree_map(lambda t, g: g + momentum * t,
                                   state["trace"], grads)
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
    return params, {"trace": trace}


OPTIMIZERS = {
    "adam": (adam_init, adam_update),
    "sgd_momentum": (momentum_init, momentum_update),
}


@jax.jit
def leaf_norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


@jax.jit
def leaf_diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
        for k in a}


#: random projections kept of every leaf of the first gradient
SKETCHES = 8


@jax.jit
def leaf_sketches(flat):
    """``{name: [SKETCHES]}``: each leaf's inner products with SKETCHES
    fixed vectors of +-1 (signs from a hash of the element's index, the
    same for whoever calls this).  Two gradients' sketches differ, in root
    mean square, by the norm of the gradients' difference, so a few numbers
    a leaf stand in for a copy of the program's whole gradient, which
    ``correct`` could not keep around until the reference has run."""
    out = {}
    for name, leaf in flat.items():
        x = leaf.astype(jnp.float32).reshape(-1)
        index = jax.lax.iota(jnp.uint32, x.size)
        rows = []
        for j in range(SKETCHES):
            h = index * jnp.uint32(2654435761) + jnp.uint32(
                (0x9E3779B9 * (j + 1)) & 0xFFFFFFFF)
            h = (h ^ (h >> 15)) * jnp.uint32(0x2C1B3C6D)
            h = (h ^ (h >> 12)) * jnp.uint32(0x297A2D39)
            sign = 1.0 - 2.0 * ((h >> 17) & jnp.uint32(1)).astype(jnp.float32)
            rows.append(jnp.sum(x * sign))
        out[name] = jnp.stack(rows)
    return out


def train_steps(loss_fn: Callable, params, batches: Sequence[tuple],
                *, optimizer: str, lr: float, rows_per_block: int) -> dict:
    """Follow ``len(batches)`` training steps in float32 and return what
    ``correct`` compares: each step's loss, the first gradient's norm and
    sketch leaf by leaf, and the norm of each leaf's change over all the
    steps.

    A batch is a tuple of host arrays whose rows are taken
    ``rows_per_block`` at a time — one chip's share — and the blocks'
    losses and gradients averaged, which is what data-parallel chips with
    an averaging all-reduce compute.  ``loss_fn(params, *arrays)`` is the
    per-block mean loss.

    The walk holds no copy of the parameters that it does not read: under
    Adam 16 bytes a parameter (parameters, two moments, one gradient), and
    one block's gradient more while a batch of several blocks is summed.
    An update gives away the moments and the gradient (the first) or the
    parameters (the later ones), so what it returns takes their place, and
    the blocks' running sum is added to and divided in place.  Only
    buffers the walk made itself are given away: ``params`` is the
    caller's to keep, and the first update reads it.  ``params`` may also
    be a function that makes the tree (``follow``: from the seed).  The
    walk then keeps no reference to what it made past the first update and
    calls the function again after the last one, so the start weights are
    not on the device through the steps."""
    init, update = OPTIMIZERS[optimizer]
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def apply(p, g, s):
        return update(p, g, s, lr=lr)

    first_step = jax.jit(apply, donate_argnums=(1, 2))
    later_step = jax.jit(apply, donate_argnums=(0, 2))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    # the divisor is an argument, as it is to an eager ``g / blocks``
    mean = jax.jit(lambda g, n: jax.tree_util.tree_map(
        lambda x: x / n, g), donate_argnums=0)
    make = params if callable(params) else (lambda tree=params: tree)
    params = make()
    state = init(params)
    losses: List[float] = []
    first_grad_norms = None
    for arrays in batches:
        rows = arrays[0].shape[0]
        if rows % rows_per_block:
            raise ValueError(f"{rows} rows do not split into blocks of "
                             f"{rows_per_block}")
        blocks = rows // rows_per_block
        loss_sum, grads = 0.0, None
        for i in range(blocks):
            part = tuple(jnp.asarray(a[i * rows_per_block:
                                       (i + 1) * rows_per_block])
                         for a in arrays)
            loss, block_grads = grad_fn(params, *part)
            loss_sum = loss_sum + loss
            grads = block_grads if grads is None else add(grads,
                                                          block_grads)
            del block_grads
            # A buffer is the allocator's until the program that reads it
            # has run, whatever Python still names: wait, or the next
            # dispatch allocates its outputs beside what was just consumed
            # (the chip read 20 B a parameter for 16: PERF.md, PR 40).
            jax.block_until_ready(grads)
        if blocks > 1:  # x / 1 is x
            grads = mean(grads, blocks)
        if first_grad_norms is None:
            first_grad_norms = leaf_norms(flatten(grads))
            first_grad_sketches = leaf_sketches(flatten(grads))
            params, state = first_step(params, grads, state)
        else:
            params, state = later_step(params, grads, state)
        jax.block_until_ready(params)
        del grads
        losses.append(float(loss_sum) / blocks)
    del state
    return {
        "losses": losses,
        "grad_norms": _to_floats(first_grad_norms),
        "grad_sketches": {k: [float(x) for x in np.asarray(v)]
                          for k, v in first_grad_sketches.items()},
        "update_norms": _to_floats(
            leaf_diff_norms(flatten(params), flatten(make()))),
    }


def follow(ref: dict, seed: int, batches: Sequence[tuple], rows: int,
           precision: str = "float32") -> dict:
    """``train_steps`` for a configuration's reference (its module's
    ``reference(cfg, mix)``), from the seed's weights, in full float32
    matmul precision — or, with ``precision="fp8"``, its control.  The
    weights are made from the seed twice, before the first step and after
    the last, and not kept between."""
    with full_precision():
        return train_steps(
            ref["loss"](precision), lambda: unflatten(ref["init"](seed)),
            batches, optimizer=ref["optimizer"], lr=ref["lr"],
            rows_per_block=rows)


def _to_floats(d) -> Dict[str, float]:
    return {k: float(np.asarray(v)) for k, v in d.items()}


def fan_in_std(shape: tuple, gain: float) -> float:
    """He et al. 2015: std = sqrt(gain / fan_in), fan_in = all dims but the
    last (HWIO kernels, [in, out] matrices)."""
    return math.sqrt(gain / float(np.prod(shape[:-1])))
