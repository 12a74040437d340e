"""Mellum2-12B-A2.5B-Instruct
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type``
``mellum``) in plain float32 ``jax.numpy``: forward, the next-token loss and,
through ``jax.grad``, the gradient — for one chip's share of the model: the
experts ``[first_expert, first_expert + num_experts)`` of each layer's
``router_num_experts`` and a vocabulary of ``vocab_size`` ids.  Written from
the source's ``config.json`` keys; nothing here comes from the program.

**Layer** ``l`` (``x`` a row of ``hidden_size``; RMSNorm is ``x / rms(x) *
w`` with a plain weight, eps ``rms_norm_eps``): ``h = x + Attn_l(norm1(x))``,
``out = h + MoE(norm2(h))``; after the last layer a final norm and an untied
head.  The layer's kind is ``layer_types[l]``; ``intermediate_size`` is read
by no layer (every ``mlp_layer_types`` entry is ``sparse``).

* ``Attn``: ``q = x W_q`` (``num_attention_heads`` heads of ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` heads), no bias, no
  head norm; rotary embedding on q and k by position, half-split pairs
  (column ``c`` with ``c + head_dim / 2``), with the table of the layer's
  kind; kv head ``g`` serves the ``heads / kv heads`` consecutive q heads
  from ``g heads / kv heads``; scores ``q . k * head_dim ** -0.5``; softmax
  over the keys the kind allows; ``o = P v``; ``o W_o``.
  ``full_attention``: row ``i`` sees key ``j`` iff ``j <= i``.
  ``sliding_attention``: iff ``j <= i`` and ``i - j < sliding_window``.  The
  allowed pairs are a dense boolean ``[rows, rows]`` array a kind, the
  scores materialised, ``QUERY_BLOCK`` queries at a time against all keys.
* Rotary tables (``rope_parameters[kind]``), float32.  ``default``:
  ``inv_freq_c = rope_theta ** (-2c / head_dim)``, ``c = 0 .. head_dim / 2 -
  1``.  ``yarn`` (Hugging Face's ``_compute_yarn_parameters``, ``truncate``
  at its default): ``e_c`` the default's, ``n_c = e_c / factor``, ``dim(r) =
  head_dim ln(original_max_position_embeddings / (2 pi r)) / (2 ln
  rope_theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
  min(ceil(dim(beta_slow)), head_dim - 1)``, ``r_c = clip((c - low) / (high -
  low), 0, 1)``, ``inv_freq_c = n_c r_c + e_c (1 - r_c)``; the table is
  ``attention_factor cos(p inv_freq)`` and the same for sin, on q and on k,
  so a full layer's scores carry the factor squared.
* ``MoE``: ``s = softmax(x W_g)`` over all ``router_num_experts`` outputs;
  the ``num_experts_per_tok`` largest (by a threshold at the sorted k-th
  largest, not ``top_k``); their weights divided by their sum
  (``norm_topk_prob``); ``sum_e w_e D_e (silu(G_e x) * U_e x)`` over the
  picks held here: a loop over the held experts, each applied to every row
  under a dense ``[rows, experts]`` matrix of weights.  Experts that live
  elsewhere add nothing.  No shared expert, no scale.  **The load bound** (a
  departure, ``assumed.expert_capacity``): the rows of a layer, in order,
  form groups of ``moe_group_rows``, and an expert keeps at most ``C =
  ceil(moe_capacity_factor * group * num_experts_per_tok /
  router_num_experts)`` of a group's picks, the first in row order; a pick
  past that is dropped with its weight, the row's other picks keep theirs.

**Loss**: mean over the ``b (s - 1)`` positions of ``logsumexp(logits_t) -
logits_t[ids_{t+1}]``, over the sliced vocabulary.

What keeps it inside one chip at 16 384 rows: each layer is recomputed in
the backward pass (``jax.checkpoint``); attention in blocks of
``QUERY_BLOCK`` queries, each recomputed in turn; the experts one at a time;
the head in blocks of ``TOKEN_BLOCK`` rows.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common

#: queries per attention block, rows per block of the head
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
SLIDING, FULL = "sliding_attention", "full_attention"


def layer_kinds(cfg: dict) -> list:
    """The kinds of the layers held here: the first ``num_hidden_layers`` of
    ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed_tokens/embedding": (cfg["vocab_size"], d),
              "norm/weight": (d,), "lm_head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        shapes[f"{p}/input_layernorm/weight"] = (d,)
        shapes[f"{p}/post_attention_layernorm/weight"] = (d,)
        a = f"{p}/self_attn"
        shapes[f"{a}/q_proj/kernel"] = (d, h * hd)
        shapes[f"{a}/k_proj/kernel"] = (d, kv * hd)
        shapes[f"{a}/v_proj/kernel"] = (d, kv * hd)
        shapes[f"{a}/o_proj/kernel"] = (h * hd, d)
        m = f"{p}/mlp"
        shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
        shapes[f"{m}/experts_gate_proj"] = (held, d, f)
        shapes[f"{m}/experts_up_proj"] = (held, d, f)
        shapes[f"{m}/experts_down_proj"] = (held, f, d)
    return shapes


def seeded_weights(cfg: dict, seed: int) -> dict:
    """normal(0, initializer_range) for every matrix and the table, but the
    q projections, which draw from normal(0, q_proj_initializer_range)
    (``assumed.weights`` says why); ones for every norm weight.  Flat,
    ``{leaf name: array}``."""
    std = cfg["initializer_range"]
    q_std = cfg.get("q_proj_initializer_range", std)

    def rule(name, shape):
        if name.endswith("/weight"):
            return ("ones",)
        return ("normal", q_std if name.endswith("q_proj/kernel") else std)

    return common.seeded_params(param_shapes(cfg), rule, seed)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- rotary embedding, a table a kind of layer -------------------------------

def yarn_range(rope: dict, head_dim: int):
    """``(low, high)`` of YaRN's ramp, in pairs of columns."""
    def dim(rotations):
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    return (max(math.floor(dim(rope["beta_fast"])), 0),
            min(math.ceil(dim(rope["beta_slow"])), head_dim - 1))


def inv_freq(rope: dict, head_dim: int):
    """``[head_dim / 2]`` float32: a pair's angle a position, by the
    kind's ``rope_type``."""
    c = jnp.arange(head_dim // 2, dtype=jnp.float32)
    extrapolation = float(rope["rope_theta"]) ** (-2.0 * c / head_dim)
    if rope["rope_type"] == "default":
        return extrapolation
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written "
                         f"here")
    interpolation = extrapolation / rope["factor"]
    low, high = yarn_range(rope, head_dim)
    if low == high:
        high += 0.001       # as the source: no division by zero
    ramp = jnp.clip((c - low) / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)


def rotary_table(rope: dict, head_dim: int, rows: int):
    """``(cos, sin)``, ``[rows, head_dim / 2]`` each, times the kind's
    ``attention_factor`` (1 where it has none)."""
    angles = jnp.arange(rows, dtype=jnp.float32)[:, None] \
        * inv_freq(rope, head_dim)[None, :]
    factor = rope.get("attention_factor", 1.0)
    return factor * jnp.cos(angles), factor * jnp.sin(angles)


def _rotary(x, table):
    """``x``: ``[b, rows, h, hd]``, column ``c`` paired with ``c + hd /
    2``."""
    cos, sin = (t[None, :, None, :] for t in table)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# -- attention ----------------------------------------------------------------

def allowed_pairs(kind: str, rows: int, window: int):
    """The dense mask of a kind of layer, ``[rows, rows]`` booleans, pair by
    pair from the definition."""
    i, j = jnp.arange(rows)[:, None], jnp.arange(rows)[None, :]
    if kind == FULL:
        return j <= i
    if kind != SLIDING:
        raise ValueError(f"layer type {kind!r} is not written here")
    return (j <= i) & (i - j < window)


def masked_attention(qh, kh, vh, seen, q, query_block: int = QUERY_BLOCK):
    """Softmax attention over the allowed pairs, ``[b, rows, h, hd]`` with
    equal head counts, ``seen`` ``[rows, rows]``; queries in blocks of
    ``query_block`` when there are more rows than that."""
    b, rows, h, hd = qh.shape
    scale = hd ** -0.5
    kq, vq = q(kh), q(vh)

    def block(args):
        qb, seen_b = args
        logits = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kq) * scale
        logits = jnp.where(seen_b[None, None], logits, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          q(jax.nn.softmax(logits, axis=-1)), vq)

    if rows <= query_block:
        return block((qh, seen))
    if rows % query_block:
        raise ValueError(f"{rows} rows are not a multiple of {query_block}")
    n = rows // query_block
    out = jax.lax.map(jax.checkpoint(block), (
        jnp.moveaxis(qh.reshape(b, n, query_block, h, hd), 1, 0),
        seen.reshape(n, query_block, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(b, rows, h, hd)


def _attention(x, p, cfg, table, seen, q):
    b, rows, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    xq = q(x)
    qh = (xq @ q(p["q_proj"]["kernel"])).reshape(b, rows, h, hd)
    kh = (xq @ q(p["k_proj"]["kernel"])).reshape(b, rows, kv, hd)
    vh = (xq @ q(p["v_proj"]["kernel"])).reshape(b, rows, kv, hd)
    qh, kh = _rotary(qh, table), _rotary(kh, table)
    kh, vh = (jnp.repeat(t, h // kv, axis=2) for t in (kh, vh))
    o = masked_attention(qh, kh, vh, seen, q)
    return q(o.reshape(b, rows, h * hd)) @ q(p["o_proj"]["kernel"])


# -- the expert layer -----------------------------------------------------------

def gate_weights(x, router, top_k: int):
    """``[n, E]``: each row's normalised weight on the ``top_k`` experts
    with the largest probability, 0 elsewhere.  The router's product is not
    rounded in the control: which experts a row picks is the routing, not
    the arithmetic under test."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    picked = jnp.where(probs >= kth, probs, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def bounded(gates, group: int, capacity: int):
    """``gates`` ``[n, experts]`` with each expert's picks past its first
    ``capacity`` of every ``group`` rows set to zero."""
    n, e = gates.shape
    place = jnp.cumsum((gates > 0).reshape(n // group, group, e), axis=1)
    return gates * (place <= capacity).reshape(n, e)


def capacity(cfg: dict, group: int) -> int:
    return math.ceil(cfg["moe_capacity_factor"] * group
                     * cfg["num_experts_per_tok"] / cfg["router_num_experts"])


def held_gates(flat, router, cfg):
    """``[n, held]``: :func:`gate_weights` on the experts held here, under
    the load bound where the configuration has one."""
    n = flat.shape[0]
    gates = gate_weights(flat, router, cfg["num_experts_per_tok"])
    gates = jax.lax.dynamic_slice_in_dim(
        gates, cfg["first_expert"], cfg["num_experts"], axis=1)
    if cfg.get("moe_capacity_factor") is not None:
        group = min(cfg.get("moe_group_rows") or n, n)
        gates = bounded(gates, group, capacity(cfg, group))
    return gates


def moe(x, p, cfg, q):
    """The held experts' part of the layer; ``x``: ``[b, rows, d]``."""
    b, rows, d = x.shape
    flat = x.reshape(b * rows, d)
    gates = held_gates(flat, p["gate"], cfg)
    xq = q(flat)

    def expert(args):
        gate_w, up_w, down_w, weight = args
        hidden = _silu(xq @ q(gate_w)) * (xq @ q(up_w))
        return weight[:, None] * (q(hidden) @ q(down_w))

    routed = jnp.sum(jax.lax.map(jax.checkpoint(expert), (
        p["experts_gate_proj"], p["experts_up_proj"], p["experts_down_proj"],
        gates.T)), axis=0)
    return routed.reshape(b, rows, d)


def _layer(x, p, cfg, table, seen, q):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_norm(x, p["input_layernorm"]["weight"], eps),
                       p["self_attn"], cfg, table, seen, q)
    return x + moe(_norm(x, p["post_attention_layernorm"]["weight"], eps),
                   p["mlp"], cfg, q)


def head_loss(x, head, targets, q, token_block: int = TOKEN_BLOCK):
    """Mean of ``logsumexp(x_i head) - (x_i head)[targets_i]``,
    ``token_block`` rows at a time."""
    n, d = x.shape
    pad = -n % token_block
    x = jnp.pad(x, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    counted = (jnp.arange(n + pad) < n).astype(jnp.float32)
    hq = q(head)

    def block(args):
        xb, tb, wb = args
        log_probs = jax.nn.log_softmax(q(xb) @ hq, axis=-1)
        picked = jnp.take_along_axis(log_probs, tb[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * wb)

    k = (n + pad) // token_block
    return jnp.sum(jax.lax.map(jax.checkpoint(block), (
        x.reshape(k, token_block, d), targets.reshape(k, token_block),
        counted.reshape(k, token_block)))) / n


def hidden_fn(cfg: dict, q):
    """``hidden(params, ids)``: ``[b, s, d]`` after the final norm; ``q``
    rounds the products' operands."""
    kinds = layer_kinds(cfg)

    def hidden(params, ids):
        rows, hd = ids.shape[1], cfg["head_dim"]
        tables = {k: rotary_table(cfg["rope_parameters"][k], hd, rows)
                  for k in set(kinds)}
        seen = {k: allowed_pairs(k, rows, cfg["sliding_window"])
                for k in set(kinds)}
        x = params["embed_tokens"]["embedding"][ids]
        for i, kind in enumerate(kinds):
            x = jax.checkpoint(
                lambda x, p, kind=kind: _layer(
                    x, p, cfg, tables[kind], seen[kind], q))(
                        x, params[f"layers_{i}"])
        return _norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])

    return hidden


def logits_fn(cfg: dict, precision: str = "float32"):
    """``logits(params, ids)``: ``[b, s, vocab]`` (small sizes: the whole
    array)."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)
    return lambda params, ids: q(hidden(params, ids)) @ q(params["lm_head"])


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, ids)``: mean cross-entropy of predicting ``ids[:, t +
    1]`` at position ``t``, over the sliced vocabulary."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)

    def loss(params, ids):
        b, s = ids.shape
        x = hidden(params, ids)
        return head_loss(x[:, :-1].reshape(b * (s - 1), -1),
                         params["lm_head"], ids[:, 1:].reshape(b * (s - 1)),
                         q)

    return loss
