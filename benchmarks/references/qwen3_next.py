"""Qwen3-Next (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct) in
plain float32 ``jax.numpy``: forward, next-token loss and, through
``jax.grad``, the gradient — for one chip's share of the model: the experts
``[first_expert, first_expert + num_experts)`` of each layer's
``router_num_experts`` and a vocabulary of ``vocab_size`` ids.

Layer ``i`` is a full-attention layer if ``(i + 1) %
full_attention_interval == 0``, else a gated-DeltaNet layer; every layer is
``x += mixer(norm(x)); x += moe(norm(x))``; RMSNorm is ``x / rms(x) * (1 +
w)``; a final norm and an untied head.

* Gated DeltaNet is **the recurrence itself**, token by token::

      S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
      o_t = S^T q_t

  with no chunk algebra.  Fused projections' columns are ``[q | k | v | z]``
  and ``[b | a]``, heads contiguous inside each (the program's order).
* Full attention is materialised softmax attention with k and v repeated to
  the q heads, queries in blocks against all keys.
* The expert layer is a loop over the held experts, each applied to every
  token under a dense ``[tokens, experts]`` matrix of gate weights: softmax
  over all the router's outputs, the ``num_experts_per_tok`` largest (by a
  threshold at the sorted k-th largest, not ``top_k``), divided by their
  sum.  Experts that live elsewhere add nothing.

Departures from the published model are the configuration's (its file's
``assumed``): no router auxiliary loss, no multi-token-prediction module.

What keeps it inside one chip at 8192 tokens: each layer is recomputed in
the backward pass (``jax.checkpoint``), and inside a layer the recurrence
runs in blocks of ``TOKEN_BLOCK`` tokens, each recomputed in turn — kept
are the states at the blocks' starts (2 MB a layer a block at 32 heads of
128 x 128) and, while one block's backward runs, that block's
``TOKEN_BLOCK`` states; attention in blocks of ``QUERY_BLOCK`` queries; the
experts one at a time; the head in blocks of tokens (``gpt2._head_loss``).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common
from .gpt2 import _head_loss

#: tokens per checkpointed block of the recurrence, queries per attention
#: block
TOKEN_BLOCK = 256
QUERY_BLOCK = 1024


def is_full_attention(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    value_dim = hv * dv
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    shapes = {"embed_tokens/embedding": (cfg["vocab_size"], d),
              "norm/weight": (d,), "lm_head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        shapes[f"{p}/input_layernorm/weight"] = (d,)
        shapes[f"{p}/post_attention_layernorm/weight"] = (d,)
        if is_full_attention(cfg, i):
            a = f"{p}/self_attn"
            shapes[f"{a}/q_proj/kernel"] = (d, h * hd * 2)
            shapes[f"{a}/k_proj/kernel"] = (d, kv * hd)
            shapes[f"{a}/v_proj/kernel"] = (d, kv * hd)
            shapes[f"{a}/q_norm/weight"] = (hd,)
            shapes[f"{a}/k_norm/weight"] = (hd,)
            shapes[f"{a}/o_proj/kernel"] = (h * hd, d)
        else:
            a = f"{p}/linear_attn"
            shapes[f"{a}/in_proj_qkvz/kernel"] = (d, 2 * key_dim
                                                  + 2 * value_dim)
            shapes[f"{a}/in_proj_ba/kernel"] = (d, 2 * hv)
            shapes[f"{a}/conv1d"] = (cfg["linear_conv_kernel_dim"],
                                     2 * key_dim + value_dim)
            shapes[f"{a}/A_log"] = (hv,)
            shapes[f"{a}/dt_bias"] = (hv,)
            shapes[f"{a}/norm"] = (dv,)
            shapes[f"{a}/out_proj/kernel"] = (value_dim, d)
        m = f"{p}/mlp"
        shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
        shapes[f"{m}/experts_gate_proj"] = (held, d, f)
        shapes[f"{m}/experts_up_proj"] = (held, d, f)
        shapes[f"{m}/experts_down_proj"] = (held, f, d)
        shapes[f"{m}/shared_gate_proj/kernel"] = (d, fs)
        shapes[f"{m}/shared_up_proj/kernel"] = (d, fs)
        shapes[f"{m}/shared_down_proj/kernel"] = (fs, d)
        shapes[f"{m}/shared_expert_gate/kernel"] = (d, 1)
    return shapes


def seeded_weights(cfg: dict, seed: int) -> dict:
    """normal(0, initializer_range) for every matrix, table and convolution
    kernel; the (1 + w) norms' weights zero and the DeltaNet output norm's
    one, as the source initialises them; ``dt_bias`` ones; ``A_log`` the
    log of uniform(0, 16) (the source's), drawn away from 0 by 1e-3.  Flat,
    ``{leaf name: array}``."""
    std = cfg["initializer_range"]

    def rule(name, shape):
        if name.endswith(("/norm", "dt_bias")):
            return ("ones",)
        if name.endswith(("/weight", "A_log")):
            return ("zeros",)
        return ("normal", std)

    flat = common.seeded_params(param_shapes(cfg), rule, seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) + 2)
    for i, name in enumerate(sorted(n for n in flat if n.endswith("A_log"))):
        flat[name] = jnp.log(jax.random.uniform(
            jax.random.fold_in(key, i), flat[name].shape, jnp.float32,
            1e-3, 16.0))
    return flat


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def _norm(x, weight, eps):
    return _rms(x, eps) * (1.0 + weight)


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- gated DeltaNet ----------------------------------------------------------

def delta_recurrence(qh, kh, vh, g, beta, q):
    """``qh``, ``kh``: ``[b, s, h, dk]``; ``vh``: ``[b, s, h, dv]``; ``g``,
    ``beta``: ``[b, s, h]``.  Returns ``o`` ``[b, s, h, dv]``.  The products
    with the state take their operands through ``q`` (the control's
    rounding)."""
    b, s, h, dk = kh.shape
    dv = vh.shape[-1]
    pad = -s % TOKEN_BLOCK
    n = (s + pad) // TOKEN_BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, TOKEN_BLOCK, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 1)  # [n, T, b, ...]

    def token(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", q(state), q(k_t))
        u = b_t[..., None] * (v_t - read)
        state = state + q(k_t)[..., :, None] * q(u)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", q(state), q(q_t))

    def block(state, rows):
        return jax.lax.scan(token, state, rows)

    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(block), state,
                        tuple(blocks(x) for x in (qh, kh, vh, g, beta)))
    o = o.reshape(n * TOKEN_BLOCK, b, h, dv)[:s]           # padded rows write 0
    return jnp.moveaxis(o, 0, 1)


def _causal_conv(x, kernel):
    """Depthwise, ``kernel`` ``[taps, c]``, left-padded by ``taps - 1``."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0
    for j in range(taps):
        out = out + padded[:, j:j + s] * kernel[j]
    return out


def _delta_net(x, p, cfg, q):
    b, s, _ = x.shape
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    eps = cfg["rms_norm_eps"]
    xq = q(x)
    qkvz = xq @ q(p["in_proj_qkvz"]["kernel"])
    ba = xq @ q(p["in_proj_ba"]["kernel"])
    qkv = _silu(_causal_conv(q(qkvz[..., :2 * key_dim + value_dim]),
                             q(p["conv1d"])))
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, s, hv, dv)
    qh = qkv[..., :key_dim].reshape(b, s, hk, dk)
    kh = qkv[..., key_dim:2 * key_dim].reshape(b, s, hk, dk)
    vh = qkv[..., 2 * key_dim:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1,
                                         keepdims=True) + eps)

    # key head i serves value heads i * r .. i * r + r - 1
    r = hv // hk
    qh = jnp.repeat(l2(qh) / math.sqrt(dk), r, axis=2)
    kh = jnp.repeat(l2(kh), r, axis=2)
    o = delta_recurrence(qh, kh, vh, g, beta, q)
    o = p["norm"] * _rms(o, eps) * _silu(z)
    return q(o.reshape(b, s, value_dim)) @ q(p["out_proj"]["kernel"])


# -- gated softmax attention -------------------------------------------------

def _rotary(x, theta, rotary_dim):
    s = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    half = rotary_dim // 2
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _softmax_attention(qh, kh, vh, q):
    """Causal, ``[b, s, h, hd]`` with equal head counts; queries in blocks
    of QUERY_BLOCK when the sequence is longer than that."""
    b, s, h, hd = qh.shape
    scale = 1.0 / math.sqrt(hd)
    kq, vq = q(kh), q(vh)
    key_pos = jnp.arange(s)

    def block(args):
        qb, start = args
        pos = start + jnp.arange(qb.shape[1])
        logits = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kq) * scale
        logits = jnp.where(key_pos[None, :] <= pos[:, None], logits,
                           -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          q(jax.nn.softmax(logits, axis=-1)), vq)

    if s <= QUERY_BLOCK:
        return block((qh, 0))
    if s % QUERY_BLOCK:
        raise ValueError(f"sequence {s} is not a multiple of {QUERY_BLOCK}")
    n = s // QUERY_BLOCK
    blocks = jnp.moveaxis(qh.reshape(b, n, QUERY_BLOCK, h, hd), 1, 0)
    out = jax.lax.map(jax.checkpoint(block),
                      (blocks, jnp.arange(n) * QUERY_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def _attention(x, p, cfg, q):
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rotary_dim = int(hd * cfg["partial_rotary_factor"])
    xq = q(x)
    qg = (xq @ q(p["q_proj"]["kernel"])).reshape(b, s, h, 2 * hd)
    qh, gate = qg[..., :hd], qg[..., hd:]
    kh = (xq @ q(p["k_proj"]["kernel"])).reshape(b, s, kv, hd)
    vh = (xq @ q(p["v_proj"]["kernel"])).reshape(b, s, kv, hd)
    qh = _rotary(_norm(qh, p["q_norm"]["weight"], eps), cfg["rope_theta"],
                 rotary_dim)
    kh = _rotary(_norm(kh, p["k_norm"]["weight"], eps), cfg["rope_theta"],
                 rotary_dim)
    kh, vh = (jnp.repeat(t, h // kv, axis=2) for t in (kh, vh))
    o = _softmax_attention(qh, kh, vh, q) * jax.nn.sigmoid(gate)
    return q(o.reshape(b, s, h * hd)) @ q(p["o_proj"]["kernel"])


# -- the expert layer ---------------------------------------------------------

def gate_weights(x, router, top_k: int):
    """``[n, E]``: each token's normalised weight on the ``top_k`` experts
    with the largest probability, 0 elsewhere.  The router's product is not
    rounded in the control: which experts a token picks is the routing, not
    the arithmetic under test."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    picked = jnp.where(probs >= kth, probs, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def _moe(x, p, cfg, q):
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    first, held = cfg["first_expert"], cfg["num_experts"]
    gates = gate_weights(flat, p["gate"], cfg["num_experts_per_tok"])
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)
    xq = q(flat)

    def expert(args):
        gate_w, up_w, down_w, weight = args
        hidden = _silu(xq @ q(gate_w)) * (xq @ q(up_w))
        return weight[:, None] * (q(hidden) @ q(down_w))

    routed = jnp.sum(jax.lax.map(jax.checkpoint(expert), (
        p["experts_gate_proj"], p["experts_up_proj"], p["experts_down_proj"],
        gates.T)), axis=0)
    hidden = _silu(xq @ q(p["shared_gate_proj"]["kernel"])) \
        * (xq @ q(p["shared_up_proj"]["kernel"]))
    shared = (q(hidden) @ q(p["shared_down_proj"]["kernel"])) \
        * jax.nn.sigmoid(xq @ q(p["shared_expert_gate"]["kernel"]))
    return (routed + shared).reshape(b, s, d)


def _layer(x, p, cfg, full_attention: bool, q):
    eps = cfg["rms_norm_eps"]
    h = _norm(x, p["input_layernorm"]["weight"], eps)
    if full_attention:
        x = x + _attention(h, p["self_attn"], cfg, q)
    else:
        x = x + _delta_net(h, p["linear_attn"], cfg, q)
    h = _norm(x, p["post_attention_layernorm"]["weight"], eps)
    return x + _moe(h, p["mlp"], cfg, q)


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, ids)``: mean cross-entropy of predicting
    ``ids[:, t + 1]`` at position ``t``, over the sliced vocabulary."""
    q = common.operand_rounding(precision)

    def loss(params, ids):
        b, s = ids.shape
        x = params["embed_tokens"]["embedding"][ids]
        for i in range(cfg["num_hidden_layers"]):
            full = is_full_attention(cfg, i)
            x = jax.checkpoint(
                lambda x, p, full=full: _layer(x, p, cfg, full, q))(
                    x, params[f"layers_{i}"])
        x = _norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])
        d = x.shape[-1]
        return _head_loss(x[:, :-1].reshape(b * (s - 1), d),
                          params["lm_head"].T,
                          ids[:, 1:].reshape(b * (s - 1)), q)

    return loss
