"""LFM2-24B-A2B (https://huggingface.co/LiquidAI/LFM2-24B-A2B,
``model_type`` ``lfm2_moe``) in plain float32 ``jax.numpy``: forward, the
next-token loss and, through ``jax.grad``, the gradient — for one chip's
share of the model: the ``num_hidden_layers`` layers of ``layer_types`` from
``first_layer`` on, the first ``num_dense_layers`` of them dense, the
experts ``[first_expert, first_expert + num_experts)`` of each expert
layer's ``router_num_experts`` and a vocabulary of ``vocab_size`` ids.
Nothing here comes from the program.

**Layer** ``i`` (``x`` a row of ``hidden_size``; RMSNorm is ``x / rms(x) *
w`` with a plain weight, eps ``norm_eps``): ``h = x + operator_i(norm(x))``,
``out = h + ff_i(norm(h))``; after the last layer a final norm and ``logits
= h @ table^T``, the table the embedding's (tied).  No bias anywhere.

* ``conv`` (``d = hidden_size``, ``L = conv_L_cache`` taps): ``[B | C | x] =
  u W_in``, three blocks of ``d`` columns in that order; ``z_t = sum_{j <
  L} w_j (B * x)_{t - (L - 1) + j}`` a channel, zeros before the sequence's
  start; ``y = (C * z) W_out``.
* ``full_attention``: ``q = x W_q`` (``num_attention_heads`` heads of
  ``head_dim = hidden_size / num_attention_heads``), ``k``, ``v``
  (``num_key_value_heads``, each serving ``heads / kv`` consecutive q
  heads); RMSNorm over each q head and each k head (one weight of
  ``head_dim`` for q, one for k) **before** the rotary embedding over the
  whole head, halves paired, theta ``rope_parameters.rope_theta``, positions
  ``0 .. s - 1``; scores ``q . k * head_dim ** -0.5``, causal softmax, ``o
  W_o``.  The scores are materialised a block of heads and of queries at a
  time against all keys (``references/kanana2.causal_attention``).
* dense feed-forward: ``W_down (silu(W_gate x) * W_up x)``, width
  ``intermediate_size``, ``TOKEN_BLOCK`` rows at a time.
* expert feed-forward: ``s = sigmoid(x W_r)`` over all the router's outputs;
  the picks are the ``num_experts_per_tok`` largest of ``s + b`` (by a
  threshold at the sorted k-th largest; ``b`` the selection bias, zeros
  unless the configuration gives ``expert_bias``); ``w_i = s_i`` on the
  picks, ``w <- routed_scaling_factor w / (sum w + 1e-6)``; ``sum_i w_i
  E_i(x)`` over the picks held here with ``E_i`` a SwiGLU of width
  ``moe_intermediate_size`` (a loop over the held experts, each applied to
  every row under a dense ``[rows, experts]`` matrix of weights; experts
  that live elsewhere add nothing).  No shared expert.  **The load bound**
  (a departure, ``assumed.expert_capacity``): the rows of a layer, in order,
  form groups of ``moe_group_rows``, and an expert keeps at most ``C =
  ceil(moe_capacity_factor * group * num_experts_per_tok /
  router_num_experts)`` of a group's picks, the first in row order.

**Loss**: mean over the ``b (s - 1)`` positions of ``logsumexp(logits_t) -
logits_t[ids_{t+1}]``, over the sliced vocabulary.

What keeps it inside one chip at 2 x 8192 rows: each layer is recomputed in
the backward pass (``jax.checkpoint``); attention in blocks of heads and
queries; the dense feed-forward and the head in blocks of rows; the experts
one at a time.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import common
# dense causal attention in blocks, an expert's load bound and the head's
# loss in blocks as ``kanana2_30b_a3b``'s reference has them
from .kanana2 import bounded, causal_attention, head_loss

CONV, ATTENTION = "conv", "full_attention"
#: what the source adds to the picks' sum before it divides by it
ROUTE_EPS = 1e-6
#: rows per block of the dense feed-forward
TOKEN_BLOCK = 2048


def kinds(cfg: dict) -> Tuple[str, ...]:
    """The operator kinds of the layers held here: ``num_hidden_layers``
    entries of the published ``layer_types`` from ``first_layer`` on."""
    first = cfg.get("first_layer", 0)
    held = tuple(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])
    if len(held) != cfg["num_hidden_layers"] \
            or set(held) - {CONV, ATTENTION}:
        raise ValueError(f"{cfg['num_hidden_layers']} layers from "
                         f"{first} out of {cfg['layer_types']!r}")
    return held


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["num_dense_layers"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fd = cfg["intermediate_size"]
    shapes = {"embed_tokens/embedding": (cfg["vocab_size"], d),
              "embedding_norm/weight": (d,)}
    for i, kind in enumerate(kinds(cfg)):
        p = f"layers_{i}"
        shapes[f"{p}/operator_norm/weight"] = (d,)
        shapes[f"{p}/ffn_norm/weight"] = (d,)
        if kind == CONV:
            shapes[f"{p}/conv/in_proj/kernel"] = (d, 3 * d)
            shapes[f"{p}/conv/conv"] = (cfg["conv_L_cache"], d)
            shapes[f"{p}/conv/out_proj/kernel"] = (d, d)
        else:
            a = f"{p}/self_attn"
            shapes[f"{a}/q_proj/kernel"] = (d, h * hd)
            shapes[f"{a}/k_proj/kernel"] = (d, kv * hd)
            shapes[f"{a}/v_proj/kernel"] = (d, kv * hd)
            shapes[f"{a}/q_layernorm/weight"] = (hd,)
            shapes[f"{a}/k_layernorm/weight"] = (hd,)
            shapes[f"{a}/out_proj/kernel"] = (h * hd, d)
        m = f"{p}/feed_forward"
        if is_dense(cfg, i):
            shapes[f"{m}/gate_proj/kernel"] = (d, fd)
            shapes[f"{m}/up_proj/kernel"] = (d, fd)
            shapes[f"{m}/down_proj/kernel"] = (fd, d)
            continue
        shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
        shapes[f"{m}/experts_gate_proj"] = (held, d, f)
        shapes[f"{m}/experts_up_proj"] = (held, d, f)
        shapes[f"{m}/experts_down_proj"] = (held, f, d)
    return shapes


def seeded_weights(cfg: dict, seed: int) -> dict:
    """normal(0, initializer_range) for every matrix, the table and the
    convolutions' taps; ones for every norm weight but the heads' q and k
    norms, which start at ``qk_norm_init`` (``assumed.weights``: those two
    weights are the softmax's temperature, ``references/sdar.py`` has the
    rule).  Flat, ``{leaf name: array}``."""
    std = cfg["initializer_range"]

    def rule(name, shape):
        if name.endswith(("q_layernorm/weight", "k_layernorm/weight")):
            return ("full", cfg["qk_norm_init"])
        return ("ones",) if name.endswith("/weight") else ("normal", std)

    return common.seeded_params(param_shapes(cfg), rule, seed)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def causal_taps(x, kernel):
    """``y_t = sum_j kernel[j] x_{t - (L - 1) + j}`` a channel; ``x`` ``[b,
    s, c]``, ``kernel`` ``[L, c]``, zeros before the sequence's start."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0
    for j in range(taps):
        out = out + padded[:, j:j + s] * kernel[j]
    return out


def short_conv(u, p, cfg, q):
    """The gated short convolution; ``u``: ``[b, s, d]``."""
    d = cfg["hidden_size"]
    bcx = q(u) @ q(p["in_proj"]["kernel"])
    gate_in, gate_out, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = causal_taps(q(gate_in * x), q(p["conv"]))
    return q(gate_out * z) @ q(p["out_proj"]["kernel"])


def _rotary(x, theta):
    """``x``: ``[b, s, h, hd]``, all of ``hd`` rotated, halves paired,
    positions ``0 .. s - 1``."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(x, p, cfg, q):
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    xq = q(x)
    qh = (xq @ q(p["q_proj"]["kernel"])).reshape(b, s, h, hd)
    kh = (xq @ q(p["k_proj"]["kernel"])).reshape(b, s, kv, hd)
    vh = (xq @ q(p["v_proj"]["kernel"])).reshape(b, s, kv, hd)
    qh = _rotary(_norm(qh, p["q_layernorm"]["weight"], eps), theta)
    kh = _rotary(_norm(kh, p["k_layernorm"]["weight"], eps), theta)
    kh, vh = (jnp.repeat(t, h // kv, axis=2) for t in (kh, vh))
    o = causal_attention(qh, kh, vh, q)
    return q(o.reshape(b, s, h * hd)) @ q(p["out_proj"]["kernel"])


def _swiglu(xq, gate, up, down, q):
    return q(_silu(xq @ q(gate)) * (xq @ q(up))) @ q(down)


def dense_mlp(x, p, q, token_block: int = TOKEN_BLOCK):
    """``x``: ``[b, s, d]``; ``token_block`` rows at a time (all of them
    when there are no more than that).  The control rounds a block's rows
    and its hidden on the block's own largest magnitude."""
    b, s, d = x.shape
    n = b * s
    block = min(token_block, n)
    if n % block:
        raise ValueError(f"{n} rows are not whole blocks of {block}")
    mats = tuple(p[f"{k}_proj"]["kernel"] for k in ("gate", "up", "down"))
    out = jax.lax.map(
        jax.checkpoint(lambda rows: _swiglu(q(rows), *mats, q)),
        x.reshape(n // block, block, d))
    return out.reshape(b, s, d)


def selection_bias(cfg: dict):
    """``b``: zeros (``assumed.selection_bias``) unless the configuration
    gives ``router_num_experts`` values."""
    return jnp.asarray(cfg.get("expert_bias")
                       or [0.0] * cfg["router_num_experts"], jnp.float32)


def gate_weights(x, router, bias, top_k: int, scale: float):
    """``[n, E]``: each row's weight on its ``top_k`` picks (the largest of
    ``sigmoid(x router) + bias``), 0 elsewhere: the scores themselves,
    without the bias, over their sum plus 1e-6, times ``scale``.  The
    router's product is not rounded in the control: which experts a row
    picks is the routing, not the arithmetic under test."""
    scores = jax.nn.sigmoid(x @ router)
    chosen = scores + bias
    kth = jnp.sort(chosen, axis=-1)[:, -top_k][:, None]
    picked = jnp.where(chosen >= kth, scores, 0.0)
    return scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                             + ROUTE_EPS)


def held_gates(flat, router, cfg):
    """``[n, held]``: the weights of :func:`gate_weights` on the experts
    held here, under the load bound where the configuration has one."""
    n = flat.shape[0]
    gates = gate_weights(flat, router, selection_bias(cfg),
                         cfg["num_experts_per_tok"],
                         cfg["routed_scaling_factor"])
    gates = jax.lax.dynamic_slice_in_dim(
        gates, cfg["first_expert"], cfg["num_experts"], axis=1)
    if cfg.get("moe_capacity_factor") is not None:
        group = min(cfg.get("moe_group_rows") or n, n)
        gates = bounded(gates, group, math.ceil(
            cfg["moe_capacity_factor"] * group * cfg["num_experts_per_tok"]
            / cfg["router_num_experts"]))
    return gates


def moe(x, p, cfg, q):
    """The held experts' part of the layer; ``x``: ``[b, s, d]``."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    gates = held_gates(flat, p["gate"], cfg)
    xq = q(flat)

    def expert(args):
        gate_w, up_w, down_w, weight = args
        return weight[:, None] * _swiglu(xq, gate_w, up_w, down_w, q)

    out = jnp.sum(jax.lax.map(jax.checkpoint(expert), (
        p["experts_gate_proj"], p["experts_up_proj"], p["experts_down_proj"],
        gates.T)), axis=0)
    return out.reshape(b, s, d)


def layer(x, p, cfg, kind: str, dense: bool, q):
    """One layer of operator ``kind`` with a dense or an expert
    feed-forward part."""
    eps = cfg["norm_eps"]
    h = _norm(x, p["operator_norm"]["weight"], eps)
    x = x + (short_conv(h, p["conv"], cfg, q) if kind == CONV
             else attention(h, p["self_attn"], cfg, q))
    h = _norm(x, p["ffn_norm"]["weight"], eps)
    if dense:
        return x + dense_mlp(h, p["feed_forward"], q)
    return x + moe(h, p["feed_forward"], cfg, q)


def hidden_fn(cfg: dict, q):
    """``hidden(params, ids)``: ``[b, s, d]`` after the final norm; ``q``
    rounds the products' operands."""
    def hidden(params, ids):
        x = params["embed_tokens"]["embedding"][ids]
        for i, kind in enumerate(kinds(cfg)):
            dense = is_dense(cfg, i)
            x = jax.checkpoint(
                lambda x, p, kind=kind, dense=dense: layer(
                    x, p, cfg, kind, dense, q))(x, params[f"layers_{i}"])
        return _norm(x, params["embedding_norm"]["weight"], cfg["norm_eps"])

    return hidden


def logits_fn(cfg: dict, precision: str = "float32"):
    """``logits(params, ids)``: ``[b, s, vocab]`` (small sizes: the whole
    array)."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)
    return lambda params, ids: q(hidden(params, ids)) @ q(
        params["embed_tokens"]["embedding"]).T


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, ids)``: mean cross-entropy of predicting ``ids[:, t +
    1]`` at position ``t``, over the sliced vocabulary, the head the
    table's transpose."""
    q = common.operand_rounding(precision)
    hidden = hidden_fn(cfg, q)

    def loss(params, ids):
        b, s = ids.shape
        x = hidden(params, ids)
        return head_loss(x[:, :-1].reshape(b * (s - 1), -1),
                         params["embed_tokens"]["embedding"].T,
                         ids[:, 1:].reshape(b * (s - 1)), q)

    return loss
