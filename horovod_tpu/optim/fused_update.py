"""Fused optimizer update: one flattened elementwise kernel per step.

The compute-anatomy profiler (PR 9) attributes a steady ~9% of the
ResNet-50 step to ``optimizer_update`` — not because the math is heavy
(SGD-momentum is 3 flops/param) but because the optax path traverses the
parameter pytree per leaf: hundreds of tiny elementwise kernels, each
paying dispatch + HBM round-trip overhead on tensors far below the VPU's
efficient tile size.  This module is the fused alternative: the gradient
and parameter pytrees are flattened into ONE contiguous buffer per dtype
and the whole update (momentum/Adam moments included) runs as a single
elementwise kernel over it — Pallas on TPU, a jnp expression off-TPU
that is bit-identical (same elementwise ops in the same order), with a
NumPy oracle for the tests (the ``numpy_adasum`` pattern, ops/adasum.py).

Three rules, matching optax expression-for-expression so parity is
pinned, not approximate:

* ``sgd``        — ``p += (-lr) * g``
* ``momentum``   — ``t = m*t + g;  p += (-lr) * t`` (optax ``trace``)
* ``adam``       — optax ``scale_by_adam`` with the same
  ``(1-b)·g + b·m`` moment updates and ``1 - b**count`` bias correction

The optimizer state is the flat layout itself
(:class:`FusedOptState`: per-dtype flat moment buffers + step count), so
the fused and per-leaf paths share ONE state pytree and the autotuner's
``fused_optimizer`` knob can flip between them through the re-jit seam
without a state migration.  ``update()`` (optax-compatible signature,
per-leaf traversal — the A side of the A/B) and :meth:`fused_update`
(the fused kernel — the B side) compute identical numbers.

Donation safety: the fused path writes fresh buffers from the flat
views; it never aliases into the (possibly donated) inputs, so a
``donate_argnums`` train state cannot observe a stale buffer
(tests/test_fused_update.py pins this against a non-donated run).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import env as env_util

#: the supported update rules (KIND values)
SGD, MOMENTUM, ADAM = "sgd", "momentum", "adam"

#: flat buffers are blocked [rows, _LANES] for the Pallas path
_LANES = 128
#: VMEM one kernel's pipelined blocks may hold in total.  The Pallas
#: pipeline double-buffers every operand, inputs and outputs alike, and
#: the default scoped limit on v5e is 16 MiB; half of it leaves the
#: compiler room for the kernel's temporaries.  The block is sized from
#: the operand count (momentum has 5, Adam 7) instead of raising the limit.
_VMEM_BUDGET_BYTES = 8 << 20
#: block rows are a multiple of the deepest sublane tile (8-bit: 32 rows)
_ROW_TILE = 32


class FusedOptState(NamedTuple):
    """Flat optimizer state: ``count`` plus per-dtype-group moment
    buffers keyed like the parameter groups (``{dtype_name: flat}``).
    SGD carries empty dicts — the structure is still fixed, so
    ``lax.scan`` carries and elastic rebuilds keep one shape."""

    count: jnp.ndarray          # int32 scalar, optax-style step counter
    mu: Dict[str, Any]          # first moment / momentum trace, or {}
    nu: Dict[str, Any]          # second moment (adam only), or {}


# ---------------------------------------------------------------------------
# flat layout
# ---------------------------------------------------------------------------
def _group_leaves(tree) -> Tuple[Dict[str, List[int]], List[Any], Any]:
    """Leaves grouped by dtype name (one fused buffer per dtype — mixed
    f32/bf16 parameter trees each get their own kernel).  Returns
    ``(groups, leaves, treedef)`` with groups mapping dtype name to leaf
    indices in flatten order."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.asarray(leaf).dtype.name, []).append(i)
    return groups, leaves, treedef


def flatten_by_dtype(tree) -> Tuple[Dict[str, jnp.ndarray], Any]:
    """``{dtype_name: 1-D flat buffer}`` plus the metadata needed to
    invert it (:func:`unflatten_by_dtype`)."""
    groups, leaves, treedef = _group_leaves(tree)
    flat = {
        name: jnp.concatenate(
            [jnp.ravel(leaves[i]) for i in idxs]) if idxs else None
        for name, idxs in groups.items()
    }
    meta = (groups, [jnp.shape(l) for l in leaves], treedef)
    return flat, meta


def unflatten_by_dtype(flat: Dict[str, jnp.ndarray], meta):
    groups, shapes, treedef = meta
    leaves: List[Any] = [None] * len(shapes)
    for name, idxs in groups.items():
        buf = flat[name]
        offset = 0
        for i in idxs:
            size = int(np.prod(shapes[i], dtype=np.int64)) if shapes[i] \
                else 1
            leaves[i] = jnp.reshape(buf[offset:offset + size], shapes[i])
            offset += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the update math — ONE definition per rule, returning the optax-style
# UPDATE (delta) plus new moments.  Both runtime paths (fused jnp and
# per-leaf) consume THESE, which is what makes the fused_optimizer
# knob-flip bit-equal by construction; the Pallas kernels and the NumPy
# oracle are independent twins of the same expressions, pinned against
# this definition by tests/test_fused_update.py.
# ---------------------------------------------------------------------------
def _sgd_update(g, lr):
    return (-lr) * g


def _momentum_update(g, t, lr, m):
    t = m * t + g
    return (-lr) * t, t


def _adam_update(g, mu, nu, lr, b1, b2, eps, inv_bc1, inv_bc2):
    """optax ``scale_by_adam`` expression order: moments as
    ``(1-b)·g + b·m``, hats via the precomputed ``1/(1-b**count)``."""
    mu = (1.0 - b1) * g + b1 * mu
    nu = (1.0 - b2) * (g * g) + b2 * nu
    step = (mu * inv_bc1) / (jnp.sqrt(nu * inv_bc2) + eps)
    return (-lr) * step, mu, nu


# -- Pallas kernels (same math over [rows, 128] VMEM blocks) ----------------
def _sgd_kernel(lr, p_ref, g_ref, o_ref):
    o_ref[...] = p_ref[...] + (-lr) * g_ref[...]


def _momentum_kernel(lr, m, p_ref, g_ref, t_ref, o_ref, tn_ref):
    t = m * t_ref[...] + g_ref[...]
    tn_ref[...] = t
    o_ref[...] = p_ref[...] + (-lr) * t


def _adam_kernel(lr, b1, b2, eps, p_ref, g_ref, mu_ref, nu_ref, bc_ref,
                 o_ref, mun_ref, nun_ref):
    g = g_ref[...]
    mu = (1.0 - b1) * g + b1 * mu_ref[...]
    nu = (1.0 - b2) * (g * g) + b2 * nu_ref[...]
    mun_ref[...] = mu
    nun_ref[...] = nu
    mu_hat = mu * bc_ref[0, 0]
    nu_hat = nu * bc_ref[0, 1]
    o_ref[...] = p_ref[...] + (-lr) * (mu_hat / (jnp.sqrt(nu_hat) + eps))


def _pad_rows(flat: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    n = flat.shape[0]
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES), n


def _pallas_elementwise(kernel, flats, n_out: int, *, scalars=()):
    """Run ``kernel`` over same-length flat buffers blocked to
    [rows, 128]; ``scalars`` (each a [1, 128] row, e.g. the Adam bias
    corrections) are appended after the flats and broadcast whole to
    every block.  Returns ``n_out`` flat buffers trimmed back to the
    unpadded length."""
    from jax.experimental import pallas as pl

    from ..ops.flash_attention import _resolve_interpret

    blocked, n = [], None
    for a in flats:
        b2, n = _pad_rows(a)
        blocked.append(b2)
    rows = blocked[0].shape[0]
    dtype = blocked[0].dtype
    per_operand = _VMEM_BUDGET_BYTES // (2 * (len(blocked) + n_out))
    cap = per_operand // (_LANES * dtype.itemsize) // _ROW_TILE * _ROW_TILE
    block = min(cap, rows)
    in_specs = [pl.BlockSpec((block, _LANES), lambda i: (i, 0))
                for _ in blocked]
    in_specs += [pl.BlockSpec((1, _LANES), lambda i: (0, 0))
                 for _ in scalars]
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block, _LANES), lambda i: (i, 0))
                   for _ in range(n_out)],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), dtype)
                   for _ in range(n_out)],
        interpret=_resolve_interpret(None),
    )(*blocked, *scalars)
    return [o.reshape(-1)[:n] for o in outs]


def _resolve_pallas(use_pallas: Optional[bool]) -> bool:
    """Pallas on real TPU, jnp elsewhere (interpret mode would be pure
    overhead); ``HVD_FUSED_UPDATE_PALLAS`` forces either way (the tests
    force it on to pin pallas-vs-jnp bit identity on CPU)."""
    env = env_util.get_str(env_util.HVD_FUSED_UPDATE_PALLAS)
    if env is not None:
        return env_util.parse_bool(env)
    if use_pallas is not None:
        return use_pallas
    from ..ops.flash_attention import _on_tpu

    return _on_tpu()


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FusedOptimizer:
    """A fusable SGD/momentum/Adam optimizer with optax-compatible
    surface (``init`` / ``update``) plus the fused entry
    (:meth:`fused_update`) the training step's ``HVD_FUSED_OPTIMIZER``
    path dispatches — both over one shared flat state layout."""

    kind: str = SGD
    learning_rate: float = 0.01
    momentum: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    use_pallas: Optional[bool] = None

    def __post_init__(self):
        if self.kind not in (SGD, MOMENTUM, ADAM):
            raise ValueError(f"unknown fused optimizer kind {self.kind!r}")

    # -- state ---------------------------------------------------------------
    def init(self, params) -> FusedOptState:
        flat, _ = flatten_by_dtype(params)
        zeros = {k: jnp.zeros_like(v) for k, v in flat.items()}
        if self.kind == SGD:
            mu, nu = {}, {}
        elif self.kind == MOMENTUM:
            mu, nu = zeros, {}
        else:
            mu = zeros
            nu = {k: jnp.zeros_like(v) for k, v in flat.items()}
        return FusedOptState(count=jnp.zeros((), jnp.int32), mu=mu, nu=nu)

    # -- the fused path (one kernel per dtype group) -------------------------
    def fused_update(self, grads, state: FusedOptState, params):
        """``(new_params, new_state)`` — flatten, one elementwise kernel
        per dtype group, unflatten.  No per-leaf traversal."""
        pf, meta = flatten_by_dtype(params)
        gf, _ = flatten_by_dtype(grads)
        count = state.count + 1
        pallas = _resolve_pallas(self.use_pallas)
        new_p: Dict[str, jnp.ndarray] = {}
        new_mu: Dict[str, jnp.ndarray] = {}
        new_nu: Dict[str, jnp.ndarray] = {}
        for name, p in pf.items():
            g = gf[name].astype(p.dtype)
            lr = p.dtype.type(self.learning_rate)
            if self.kind == SGD:
                if pallas:
                    (o,) = _pallas_elementwise(
                        partial(_sgd_kernel, lr), [p, g], 1)
                else:
                    o = p + _sgd_update(g, lr)
                new_p[name] = o
            elif self.kind == MOMENTUM:
                m = p.dtype.type(self.momentum)
                if pallas:
                    o, t = _pallas_elementwise(
                        partial(_momentum_kernel, lr, m),
                        [p, g, state.mu[name]], 2)
                else:
                    u, t = _momentum_update(g, state.mu[name], lr, m)
                    o = p + u
                new_p[name], new_mu[name] = o, t
            else:
                inv_bc1, inv_bc2 = self._bias_corrections(count, p.dtype)
                if pallas:
                    bc = jnp.zeros((1, _LANES), p.dtype)
                    bc = bc.at[0, 0].set(inv_bc1).at[0, 1].set(inv_bc2)
                    o, mu, nu = _pallas_elementwise(
                        partial(_adam_kernel, lr, p.dtype.type(self.b1),
                                p.dtype.type(self.b2),
                                p.dtype.type(self.eps)),
                        [p, g, state.mu[name], state.nu[name]],
                        3, scalars=[bc])
                else:
                    u, mu, nu = _adam_update(
                        g, state.mu[name], state.nu[name], lr,
                        p.dtype.type(self.b1), p.dtype.type(self.b2),
                        p.dtype.type(self.eps), inv_bc1, inv_bc2)
                    o = p + u
                new_p[name], new_mu[name], new_nu[name] = o, mu, nu
        return (unflatten_by_dtype(new_p, meta),
                FusedOptState(count=count, mu=new_mu, nu=new_nu))

    def _bias_corrections(self, count, dtype):
        c = count.astype(jnp.float32)
        inv1 = (1.0 / (1.0 - jnp.power(jnp.float32(self.b1), c))).astype(dtype)
        inv2 = (1.0 / (1.0 - jnp.power(jnp.float32(self.b2), c))).astype(dtype)
        return inv1, inv2

    # -- the per-leaf reference path (optax-compatible) ----------------------
    def update(self, grads, state: FusedOptState, params=None):
        """optax signature: ``(updates, new_state)`` with per-leaf
        traversal — the unfused A side the autotuner's knob compares
        against.  Same math, same flat state layout."""
        del params
        gf_tree_groups, g_leaves, treedef = _group_leaves(grads)
        count = state.count + 1
        upd_leaves: List[Any] = [None] * len(g_leaves)
        new_mu: Dict[str, jnp.ndarray] = {}
        new_nu: Dict[str, jnp.ndarray] = {}
        for name, idxs in gf_tree_groups.items():
            # per-leaf views of the flat moment buffers
            sizes = [int(np.prod(jnp.shape(g_leaves[i]), dtype=np.int64))
                     if jnp.shape(g_leaves[i]) else 1 for i in idxs]
            offs = np.concatenate([[0], np.cumsum(sizes)])
            dtype = jnp.asarray(g_leaves[idxs[0]]).dtype
            lr = dtype.type(self.learning_rate)
            mu_parts, nu_parts = [], []
            for j, i in enumerate(idxs):
                g = g_leaves[i]
                shape = jnp.shape(g)
                if self.kind == SGD:
                    upd_leaves[i] = _sgd_update(g, lr)
                elif self.kind == MOMENTUM:
                    t = state.mu[name][offs[j]:offs[j + 1]].reshape(shape)
                    upd_leaves[i], t = _momentum_update(
                        g, t, lr, dtype.type(self.momentum))
                    mu_parts.append(jnp.ravel(t))
                else:
                    mu = state.mu[name][offs[j]:offs[j + 1]].reshape(shape)
                    nu = state.nu[name][offs[j]:offs[j + 1]].reshape(shape)
                    inv_bc1, inv_bc2 = self._bias_corrections(count, dtype)
                    upd_leaves[i], mu, nu = _adam_update(
                        g, mu, nu, lr, dtype.type(self.b1),
                        dtype.type(self.b2), dtype.type(self.eps),
                        inv_bc1, inv_bc2)
                    mu_parts.append(jnp.ravel(mu))
                    nu_parts.append(jnp.ravel(nu))
            if mu_parts:
                new_mu[name] = jnp.concatenate(mu_parts)
            if nu_parts:
                new_nu[name] = jnp.concatenate(nu_parts)
        updates = jax.tree_util.tree_unflatten(treedef, upd_leaves)
        return updates, FusedOptState(count=count, mu=new_mu, nu=new_nu)

    # -- test twins ----------------------------------------------------------
    @property
    def reference(self):
        """The exact optax construction this rule mirrors (parity
        oracle for the tests — NOT used on any runtime path)."""
        import optax

        if self.kind == ADAM:
            return optax.adam(self.learning_rate, b1=self.b1, b2=self.b2,
                              eps=self.eps)
        return optax.sgd(self.learning_rate,
                         momentum=self.momentum or None)


def fused_sgd(learning_rate: float, momentum: float = 0.0,
              **kw) -> FusedOptimizer:
    return FusedOptimizer(kind=MOMENTUM if momentum else SGD,
                          learning_rate=learning_rate, momentum=momentum,
                          **kw)


def fused_adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8, **kw) -> FusedOptimizer:
    return FusedOptimizer(kind=ADAM, learning_rate=learning_rate, b1=b1,
                          b2=b2, eps=eps, **kw)


# ---------------------------------------------------------------------------
# NumPy oracle (the adasum pattern: pure numpy, used only by tests)
# ---------------------------------------------------------------------------
def numpy_fused_update(opt: FusedOptimizer, params, grads,
                       state: Optional[dict] = None):
    """Reference implementation over numpy pytrees.  ``state`` is
    ``{"count": int, "mu": {leaf_path_index: array}, ...}`` keyed by
    flatten order; returns ``(new_params, new_state)``."""
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_flatten(grads)[0]
    if state is None:
        state = {"count": 0,
                 "mu": [np.zeros_like(np.asarray(p)) for p in p_leaves],
                 "nu": [np.zeros_like(np.asarray(p)) for p in p_leaves]}
    count = state["count"] + 1
    out, mus, nus = [], [], []
    for i, (p, g) in enumerate(zip(p_leaves, g_leaves)):
        p = np.asarray(p)
        g = np.asarray(g, p.dtype)
        lr = p.dtype.type(opt.learning_rate)
        if opt.kind == SGD:
            out.append(p + (-lr) * g)
            mus.append(state["mu"][i])
            nus.append(state["nu"][i])
        elif opt.kind == MOMENTUM:
            m = p.dtype.type(opt.momentum)
            t = m * state["mu"][i] + g
            out.append(p + (-lr) * t)
            mus.append(t)
            nus.append(state["nu"][i])
        else:
            b1 = p.dtype.type(opt.b1)
            b2 = p.dtype.type(opt.b2)
            mu = (1 - b1) * g + b1 * state["mu"][i]
            nu = (1 - b2) * (g * g) + b2 * state["nu"][i]
            mu_hat = mu / (1 - np.float32(opt.b1) ** count)
            nu_hat = nu / (1 - np.float32(opt.b2) ** count)
            out.append(p + (-lr) * (mu_hat / (np.sqrt(nu_hat)
                                              + p.dtype.type(opt.eps))))
            mus.append(mu)
            nus.append(nu)
    return (jax.tree_util.tree_unflatten(treedef, out),
            {"count": count, "mu": mus, "nu": nus})
