"""SDAR (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``) trained by block diffusion, in plain float32 ``jax.numpy``:
forward, the masked-token loss and, through ``jax.grad``, the gradient —
for one chip's share of the model: the experts ``[first_expert,
first_expert + num_experts)`` of each layer's ``router_num_experts`` and a
vocabulary of ``vocab_size`` ids whose last is ``[MASK]``.

**Data.**  A row is ``x0[0..L)`` (ids), ``level[0..L/B)`` and
``draw[0..L)``, integers from the traffic.  ``t_b = level_b / 65536`` is
block ``b``'s mask probability, ``m_i = draw_i < level_{i // B}`` says
whether token ``i`` is noised, ``xt_i = MASK if m_i else x0_i``.  The
decoder's rows are ``z = [xt ; x0]`` (``2L``), with positions ``p = [0..L) ;
[0..L)``, copy ``c = 0`` for the noised half and ``1`` for the clean half,
block ``g_i = p_i // B``.

**Decoder layer** (Qwen3-MoE's, as ``sdar_moe`` reuses it): ``h = x +
Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; RMSNorm is ``x / rms(x) *
w`` with a plain weight.

* ``Attn``: ``q = Wq u``, ``k = Wk u``, ``v = Wv u`` as ``num_attention_heads``
  / ``num_key_value_heads`` / ``num_key_value_heads`` heads of ``head_dim``;
  RMSNorm over each head's dims on q and on k (one weight of ``head_dim``
  each, shared by the heads); rotary embedding over all of a head's dims,
  half-rotation form, ``rope_theta``, angles from ``p``; each kv head
  serves ``heads / kv heads`` consecutive query heads; scores ``q k^T /
  sqrt(head_dim)``; **pair (i, j) is allowed iff** ``(c_j = 1 and g_j < g_i +
  c_i) or (c_i = 0 and c_j = 0 and g_j = g_i)`` — a clean row sees the clean
  blocks up to its own, a noised row the clean blocks before its own and
  the noised tokens of its own block, nothing sees another block's noise;
  softmax over the allowed pairs; ``Wo``.  The mask is a dense boolean
  ``[2L, 2L]`` array, the scores materialised, queries in blocks against
  all keys.
* ``MoE``: ``s = softmax(Wr u)`` over all the router's outputs, the
  ``num_experts_per_tok`` largest (by a threshold at the sorted k-th
  largest, not ``top_k``), their weights divided by their sum, ``sum_j w_j
  down_j(silu(gate_j u) * up_j u)`` over the picks held here: a loop over
  the held experts, each applied to every row under a dense ``[rows,
  experts]`` matrix of gate weights.  Experts that live elsewhere add
  nothing.  No shared expert, no bias.  **The load bound** (a departure,
  ``assumed.expert_capacity``): the rows of a layer, in order, form groups
  of ``moe_group_rows``, and an expert keeps at most ``C =
  ceil(moe_capacity_factor * group * num_experts_per_tok /
  router_num_experts)`` of a group's picks, the first in row order (GShard's
  capacity: a cumulative count of the picks down the rows, compared with
  ``C``); a pick past that is dropped with its weight, the row's other
  picks keep theirs.

Final RMSNorm and the untied head **on the noised half only** (``L`` rows).
**Loss**: ``(1 / L) sum_i m_i (1 / t_{i // B}) (logsumexp(logits_i) -
logits_i[x0_i])``, no shift (a masked position predicts its own token),
mean over the rows of a batch.

Departures from the published model are the configuration's (its file's
``assumed``): the block length, the linear schedule clipped at 1/16 (by
the traffic's levels), ``[MASK]`` as the slice's last id, no router
auxiliary loss, the experts' load bound, the q / k norms' initial weight.

What keeps it inside one chip at ``2 x 8192`` rows: each layer is
recomputed in the backward pass (``jax.checkpoint``); attention in blocks
of ``QUERY_BLOCK`` queries, each recomputed in turn; the experts one at a
time; the head in blocks of ``TOKEN_BLOCK`` rows.
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
#: a noise level is an integer in [0, LEVELS]
LEVELS = 65536


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
        shapes[f"{a}/q_norm/weight"] = (hd,)
        shapes[f"{a}/k_norm/weight"] = (hd,)
        shapes[f"{a}/o_proj/kernel"] = (h * hd, d)
        m = f"{p}/mlp"
        shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
        shapes[f"{m}/experts_gate_proj"] = (held, d, f)
        shapes[f"{m}/experts_up_proj"] = (held, d, f)
        shapes[f"{m}/experts_down_proj"] = (held, f, d)
    return shapes


def seeded_weights(cfg: dict, seed: int) -> dict:
    """normal(0, initializer_range) for every matrix and the table, ones
    for every norm weight but the heads' q and k norms, which start at
    ``qk_norm_init``.  Those two weights are the softmax's temperature (the
    projections' scale cancels in the norm: the scores' deviation is their
    product): at one, every row averages thousands of keys into one vector
    all rows share, and a layer's routers pick the same few experts for
    nearly every row (the configuration's ``assumed.weights`` has the
    readings).  Flat, ``{leaf name: array}``."""
    std = cfg["initializer_range"]

    def rule(name, shape):
        if name.endswith(("q_norm/weight", "k_norm/weight")):
            return ("full", cfg["qk_norm_init"])
        return ("ones",) if name.endswith("/weight") else ("normal", std)

    return common.seeded_params(param_shapes(cfg), rule, seed)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def noised(ids, level, draw, block: int):
    """``m``: ``[b, L]`` booleans."""
    return draw < jnp.repeat(level, block, axis=1)


def allowed_pairs(length: int, block: int):
    """The dense mask, ``[2 length, 2 length]`` booleans, pair by pair from
    the definition."""
    i = jnp.arange(2 * length)
    c, g = i // length, (i % length) // block
    ci, gi, cj, gj = c[:, None], g[:, None], c[None, :], g[None, :]
    return ((cj == 1) & (gj < gi + ci)) | ((ci == 0) & (cj == 0)
                                           & (gj == gi))


def _rotary(x, positions, theta):
    """``x``: ``[b, rows, h, hd]``, all of ``hd`` rotated, halves paired."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def masked_attention(qh, kh, vh, seen, q, query_block: int = QUERY_BLOCK):
    """Softmax attention over the allowed pairs, ``[b, rows, h, hd]`` with
    equal head counts, ``seen`` ``[rows, rows]``; queries in blocks of
    ``query_block`` when there are more rows than that."""
    b, rows, h, hd = qh.shape
    scale = 1.0 / math.sqrt(hd)
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


def _attention(x, p, cfg, positions, seen, q):
    b, rows, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    xq = q(x)
    qh = (xq @ q(p["q_proj"]["kernel"])).reshape(b, rows, h, hd)
    kh = (xq @ q(p["k_proj"]["kernel"])).reshape(b, rows, kv, hd)
    vh = (xq @ q(p["v_proj"]["kernel"])).reshape(b, rows, kv, hd)
    qh = _rotary(_norm(qh, p["q_norm"]["weight"], eps), positions, theta)
    kh = _rotary(_norm(kh, p["k_norm"]["weight"], eps), positions, theta)
    kh, vh = (jnp.repeat(t, h // kv, axis=2) for t in (kh, vh))
    o = masked_attention(qh, kh, vh, seen, q)
    return q(o.reshape(b, rows, h * hd)) @ q(p["o_proj"]["kernel"])


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


def moe(x, p, cfg, q):
    """The held experts' part of the layer; ``x``: ``[b, rows, d]``."""
    b, rows, d = x.shape
    flat = x.reshape(b * rows, d)
    gates = gate_weights(flat, p["gate"], cfg["num_experts_per_tok"])
    gates = jax.lax.dynamic_slice_in_dim(gates, cfg["first_expert"],
                                         cfg["num_experts"], axis=1)
    if cfg.get("moe_capacity_factor") is not None:
        group = min(cfg.get("moe_group_rows") or b * rows, b * rows)
        gates = bounded(gates, group, math.ceil(
            cfg["moe_capacity_factor"] * group * cfg["num_experts_per_tok"]
            / cfg["router_num_experts"]))
    xq = q(flat)

    def expert(args):
        gate_w, up_w, down_w, weight = args
        hidden = _silu(xq @ q(gate_w)) * (xq @ q(up_w))
        return weight[:, None] * (q(hidden) @ q(down_w))

    routed = jnp.sum(jax.lax.map(jax.checkpoint(expert), (
        p["experts_gate_proj"], p["experts_up_proj"], p["experts_down_proj"],
        gates.T)), axis=0)
    return routed.reshape(b, rows, d)


def _layer(x, p, cfg, positions, seen, q):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_norm(x, p["input_layernorm"]["weight"], eps),
                       p["self_attn"], cfg, positions, seen, q)
    return x + moe(_norm(x, p["post_attention_layernorm"]["weight"], eps),
                   p["mlp"], cfg, q)


def weighted_head_loss(x, head, targets, weights, q,
                       token_block: int = TOKEN_BLOCK):
    """``sum_i weights_i (logsumexp(x_i head) - (x_i head)[targets_i])``,
    ``token_block`` rows at a time."""
    n, d = x.shape
    pad = -n % token_block
    x = jnp.pad(x, ((0, pad), (0, 0)))
    targets, weights = jnp.pad(targets, (0, pad)), jnp.pad(weights, (0, pad))
    hq = q(head)

    def block(args):
        xb, tb, wb = args
        logits = q(xb) @ hq
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(log_probs, tb[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * wb)

    k = (n + pad) // token_block
    return jnp.sum(jax.lax.map(jax.checkpoint(block), (
        x.reshape(k, token_block, d), targets.reshape(k, token_block),
        weights.reshape(k, token_block))))


def hidden_fn(cfg: dict, q):
    """``hidden(params, ids, level, draw)``: ``[b, L, d]``, the noised
    half's rows after the final norm; ``q`` rounds the products'
    operands."""
    def hidden(params, ids, level, draw):
        length, block = ids.shape[1], cfg["block_length"]
        xt = jnp.where(noised(ids, level, draw, block),
                       cfg["mask_token_id"], ids)
        z = jnp.concatenate([xt, ids], axis=1)
        positions = jnp.concatenate([jnp.arange(length)] * 2)
        seen = allowed_pairs(length, block)
        x = params["embed_tokens"]["embedding"][z]
        for i in range(cfg["num_hidden_layers"]):
            x = jax.checkpoint(
                lambda x, p: _layer(x, p, cfg, positions, seen, q))(
                    x, params[f"layers_{i}"])
        return _norm(x[:, :length], params["norm"]["weight"],
                     cfg["rms_norm_eps"])

    return hidden


def logits_fn(cfg: dict, precision: str = "float32"):
    """``logits(params, ids, level, draw)``: ``[b, L, vocab]``, the noised
    half's rows (small sizes: the whole array)."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)
    return lambda params, *batch: q(hidden(params, *batch)) @ q(
        params["lm_head"])


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, ids, level, draw)``: the masked-token loss above."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)

    def loss(params, ids, level, draw):
        b, length = ids.shape
        block = cfg["block_length"]
        x = hidden(params, ids, level, draw)
        t = jnp.repeat(level.astype(jnp.float32) / LEVELS, block, axis=1)
        weights = noised(ids, level, draw, block) / t / (b * length)
        return weighted_head_loss(
            x.reshape(b * length, -1), params["lm_head"],
            ids.reshape(b * length), weights.reshape(b * length), q)

    return loss
