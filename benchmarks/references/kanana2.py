"""kanana-2-30b-a3b-instruct-2601
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601,
``model_type`` ``deepseek_v3``) in plain float32 ``jax.numpy``: forward, the
next-token loss and, through ``jax.grad``, the gradient — for one chip's
share of the model: the experts ``[first_expert, first_expert +
n_routed_experts)`` of each expert layer's ``router_num_experts`` and a
vocabulary of ``vocab_size`` ids.  Nothing here comes from the program.

**Layer** (``x`` a row of ``hidden_size``; RMSNorm is ``x / rms(x) * w``
with a plain weight, eps ``rms_norm_eps``): ``h = x + Attn(norm1(x))``,
``out = h + FFN(norm2(h))``; a final norm and an untied head.

* ``Attn`` (latent attention, no q compression): ``q = x W_q``,
  ``num_attention_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``
  = ``[q_nope | q_rope]``; ``[c | k_rope] = x W_kva`` with ``c`` of
  ``kv_lora_rank`` and *one* ``k_rope`` of ``qk_rope_head_dim`` for all
  heads; ``c <- norm_kv(c)``; ``[k_nope | v] = c W_kvb``, a head
  ``qk_nope_head_dim + v_head_dim``; rotary embedding (``rope_theta``, over
  ``qk_rope_head_dim``, ``rope_interleave``: the pairs ``(2i, 2i + 1)`` are
  rotated by ``position * theta ** (-2i / qk_rope_head_dim)``, here in
  place) on ``q_rope`` and ``k_rope``; ``k = [k_nope | k_rope]`` a head;
  scores ``q . k * (nope + rope) ** -0.5``, causal softmax, ``o = P v``,
  ``o W_o``.  The scores are materialised, ``HEAD_BLOCK`` heads and
  ``QUERY_BLOCK`` queries at a time against all keys.
* ``FFN``, layer ``i < first_k_dense_replace``: ``W_down(silu(W_gate x) *
  W_up x)``, width ``intermediate_size``.
* ``FFN``, every other layer: ``s = sigmoid(x W_g)`` over all the router's
  outputs; the picks are the ``num_experts_per_tok`` largest of ``s + b``
  (by a threshold at the sorted k-th largest, not ``top_k``; ``b`` the
  selection bias, zeros here: ``assumed.selection_bias``); ``w_i = s_i`` on
  the picks, ``w <- w / (sum w + 1e-20)``, ``w <- routed_scaling_factor
  w``; ``sum_i w_i E_i(x)`` over the picks held here (a loop over the held
  experts, each applied to every row under a dense ``[rows, experts]``
  matrix of weights; experts that live elsewhere add nothing) plus ``S(x)``,
  one SwiGLU of ``n_shared_experts * moe_intermediate_size``.  **The load
  bound** (a departure, ``assumed.expert_capacity``): the rows of a layer,
  in order, form groups of ``moe_group_rows``, and an expert keeps at most
  ``C = ceil(moe_capacity_factor * group * num_experts_per_tok /
  router_num_experts)`` of a group's picks, the first in row order; a pick
  past that is dropped with its weight.

**Loss**: mean over the ``b (s - 1)`` positions of ``logsumexp(logits_t) -
logits_t[ids_{t+1}]``, over the sliced vocabulary.

What keeps it inside one chip at 8192 rows: each layer is recomputed in the
backward pass (``jax.checkpoint``); attention in blocks of heads and
queries, each recomputed in turn; the experts one at a time; the head in
blocks of ``TOKEN_BLOCK`` rows.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common

#: heads and queries per attention block, rows per block of the head
HEAD_BLOCK = 8
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    shapes = {"embed_tokens/embedding": (cfg["vocab_size"], d),
              "norm/weight": (d,), "lm_head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}"
        shapes[f"{p}/input_layernorm/weight"] = (d,)
        shapes[f"{p}/post_attention_layernorm/weight"] = (d,)
        a = f"{p}/self_attn"
        shapes[f"{a}/q_proj/kernel"] = (d, h * (nope + rope))
        shapes[f"{a}/kv_a_proj_with_mqa/kernel"] = (d, rank + rope)
        shapes[f"{a}/kv_a_layernorm/weight"] = (rank,)
        shapes[f"{a}/kv_b_proj/kernel"] = (rank, h * (nope + dv))
        shapes[f"{a}/o_proj/kernel"] = (h * dv, d)
        m = f"{p}/mlp"
        if is_dense(cfg, i):
            shapes[f"{m}/gate_proj/kernel"] = (d, fd)
            shapes[f"{m}/up_proj/kernel"] = (d, fd)
            shapes[f"{m}/down_proj/kernel"] = (fd, d)
            continue
        shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
        shapes[f"{m}/experts_gate_proj"] = (held, d, f)
        shapes[f"{m}/experts_up_proj"] = (held, d, f)
        shapes[f"{m}/experts_down_proj"] = (held, f, d)
        shapes[f"{m}/shared_experts_gate_proj/kernel"] = (d, fs)
        shapes[f"{m}/shared_experts_up_proj/kernel"] = (d, fs)
        shapes[f"{m}/shared_experts_down_proj/kernel"] = (fs, d)
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


def _rotary(x, theta):
    """``x``: ``[b, s, h, r]``; each pair ``(x[2i], x[2i + 1])`` rotated in
    place by ``position * theta ** (-2i / r)``."""
    b, s, h, r = x.shape
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    pairs = x.reshape(b, s, h, r // 2, 2)
    first, second = pairs[..., 0], pairs[..., 1]
    return jnp.stack([first * cos - second * sin,
                      second * cos + first * sin], axis=-1).reshape(x.shape)


def causal_attention(qh, kh, vh, q, head_block: int = HEAD_BLOCK,
                     query_block: int = QUERY_BLOCK):
    """Causal softmax attention, ``qh`` and ``kh`` ``[b, s, h, dk]``, ``vh``
    ``[b, s, h, dv]``, scores scaled by ``dk ** -0.5``; ``head_block``
    heads and ``query_block`` queries at a time (all of either when there
    are no more than that)."""
    b, s, h, dk = qh.shape
    scale = dk ** -0.5
    head_block, query_block = min(head_block, h), min(query_block, s)
    if h % head_block or s % query_block:
        raise ValueError(f"{h} heads / {s} rows are not whole blocks of "
                         f"{head_block} / {query_block}")
    nh, nq = h // head_block, s // query_block
    kq, vq = q(kh), q(vh)

    def rows(args):
        qb, first, kb, vb = args
        logits = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kb) * scale
        seen = (first + jnp.arange(query_block))[:, None] \
            >= jnp.arange(s)[None, :]
        logits = jnp.where(seen[None, None], logits, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          q(jax.nn.softmax(logits, axis=-1)), vb)

    def heads(args):
        qb, kb, vb = args          # [b, s, head_block, *]
        out = jax.lax.map(jax.checkpoint(
            lambda a: rows((a[0], a[1], kb, vb))), (
            jnp.moveaxis(qb.reshape(b, nq, query_block, head_block, dk),
                         1, 0),
            jnp.arange(nq) * query_block))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, head_block, -1)

    split = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(b, s, nh, head_block, t.shape[-1]), 2, 0)
    out = jax.lax.map(heads, (split(qh), split(kq), split(vq)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h, -1)


def _attention(x, p, cfg, q):
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    xq = q(x)
    qh = (xq @ q(p["q_proj"]["kernel"])).reshape(b, s, h, nope + rope)
    ckr = xq @ q(p["kv_a_proj_with_mqa"]["kernel"])
    c = _norm(ckr[..., :rank], p["kv_a_layernorm"]["weight"],
              cfg["rms_norm_eps"])
    kv = (q(c) @ q(p["kv_b_proj"]["kernel"])).reshape(b, s, h, nope + dv)
    k_rope = _rotary(ckr[..., rank:].reshape(b, s, 1, rope), theta)
    qh = jnp.concatenate([qh[..., :nope], _rotary(qh[..., nope:], theta)],
                         axis=-1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.tile(k_rope, (1, 1, h, 1))], axis=-1)
    o = causal_attention(qh, kh, kv[..., nope:], q)
    return q(o.reshape(b, s, h * dv)) @ q(p["o_proj"]["kernel"])


def _swiglu(xq, gate, up, down, q):
    return q(_silu(xq @ q(gate)) * (xq @ q(up))) @ q(down)


def gate_weights(x, router, bias, top_k: int, scale: float):
    """``[n, E]``: each row's weight on its ``top_k`` picks (the largest of
    ``sigmoid(x router) + bias``), 0 elsewhere: the scores themselves,
    without the bias, over their sum, times ``scale``.  The router's
    product is not rounded in the control: which experts a row picks is the
    routing, not the arithmetic under test."""
    scores = jax.nn.sigmoid(x @ router)
    chosen = scores + bias
    kth = jnp.sort(chosen, axis=-1)[:, -top_k][:, None]
    picked = jnp.where(chosen >= kth, scores, 0.0)
    return scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def bounded(gates, group: int, capacity: int):
    """``gates`` ``[n, experts]`` with each expert's picks past its first
    ``capacity`` of every ``group`` rows set to zero."""
    n, e = gates.shape
    place = jnp.cumsum((gates > 0).reshape(n // group, group, e), axis=1)
    return gates * (place <= capacity).reshape(n, e)


def selection_bias(cfg: dict):
    """``b``: zeros (``assumed.selection_bias``) unless the configuration
    gives ``router_num_experts`` values."""
    return jnp.asarray(cfg.get("e_score_correction_bias")
                       or [0.0] * cfg["router_num_experts"], jnp.float32)


def held_gates(flat, router, cfg):
    """``[n, held]``: the weights of :func:`gate_weights` on the experts
    held here, under the load bound where the configuration has one."""
    n = flat.shape[0]
    gates = gate_weights(flat, router, selection_bias(cfg),
                         cfg["num_experts_per_tok"],
                         cfg["routed_scaling_factor"])
    gates = jax.lax.dynamic_slice_in_dim(
        gates, cfg["first_expert"], cfg["n_routed_experts"], axis=1)
    if cfg.get("moe_capacity_factor") is not None:
        group = min(cfg.get("moe_group_rows") or n, n)
        gates = bounded(gates, group, math.ceil(
            cfg["moe_capacity_factor"] * group * cfg["num_experts_per_tok"]
            / cfg["router_num_experts"]))
    return gates


def moe(x, p, cfg, q, shared: bool = True):
    """The held experts' part of the layer plus, with ``shared``, the
    shared experts'; ``x``: ``[b, s, d]``."""
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
    if shared:
        out = out + _swiglu(xq, *(p[f"shared_experts_{k}_proj"]["kernel"]
                                  for k in ("gate", "up", "down")), q)
    return out.reshape(b, s, d)


def _layer(x, p, cfg, dense: bool, q):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_norm(x, p["input_layernorm"]["weight"], eps),
                       p["self_attn"], cfg, q)
    h = _norm(x, p["post_attention_layernorm"]["weight"], eps)
    if dense:
        m = p["mlp"]
        return x + _swiglu(q(h), m["gate_proj"]["kernel"],
                           m["up_proj"]["kernel"], m["down_proj"]["kernel"],
                           q)
    return x + moe(h, p["mlp"], cfg, q)


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
    def hidden(params, ids):
        x = params["embed_tokens"]["embedding"][ids]
        for i in range(cfg["num_hidden_layers"]):
            dense = is_dense(cfg, i)
            x = jax.checkpoint(
                lambda x, p, dense=dense: _layer(x, p, cfg, dense, q))(
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
