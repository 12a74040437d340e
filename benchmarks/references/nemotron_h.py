"""NVIDIA-Nemotron-3-Nano-30B-A3B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` ``nemotron_h``) in plain float32 ``jax.numpy``: forward, the
next-token loss and, through ``jax.grad``, the gradient — for one chip's
share of the model: the first ``num_hidden_layers`` blocks of
``hybrid_override_pattern``, the experts ``[first_expert, first_expert +
n_routed_experts)`` of each expert block's ``router_num_experts`` and a
vocabulary of ``vocab_size`` ids.  Nothing here comes from the program.

**Block** ``i`` of kind ``k = hybrid_override_pattern[i]`` (``x`` a row of
``hidden_size``; RMSNorm is ``x / rms(x) * w`` with a plain weight, eps
``norm_eps``): ``x <- x + mixer_k(norm(x))``; a final norm and an untied
head.  No bias but the convolution's; no position signal anywhere.

* ``M`` (Mamba-2; ``H = mamba_num_heads``, ``P = mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = u W_in`` with
  widths ``H P | H P + 2 G N | H``; ``xBC <- silu(conv(xBC) + b_conv)``, a
  causal depthwise convolution of ``conv_kernel`` taps; ``x`` ``[H, P]``,
  ``B`` and ``C`` ``[G, N]``, group ``g`` serving heads ``g H / G ..``;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``, token
  by token from ``h_0 = 0``; ``y <- y * silu(z)``, then ``y / rms(y) * w``
  over each of the ``G`` groups of ``H P / G`` columns; ``y W_out``.
* ``*``: ``q = x W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k``,
  ``v`` (``num_key_value_heads``, each serving ``heads / kv`` consecutive q
  heads), scores ``q . k * head_dim ** -0.5``, causal softmax, ``o W_o``.
  The scores are materialised a block of heads and of queries at a time
  against all keys (``references/kanana2.causal_attention``).
* ``E``: ``s = sigmoid(x W_g)`` over all the router's outputs; the picks are
  the ``num_experts_per_tok`` largest of ``s + b`` (by a threshold at the
  sorted k-th largest; ``b`` the selection bias, zeros here:
  ``assumed.selection_bias``); ``w_i = s_i`` on the picks, ``w <- w / (sum
  w + 1e-20)``, ``w <- routed_scaling_factor w``; ``sum_i w_i E_i(x)`` over
  the picks held here with ``E_i(x) = W_down,i relu(W_up,i x) ** 2`` (a loop
  over the held experts, each applied to every row under a dense ``[rows,
  experts]`` matrix of weights; experts that live elsewhere add nothing)
  plus ``S(x)``, one expert of the same form and width
  ``moe_shared_expert_intermediate_size``.  **The load bound** (a
  departure, ``assumed.expert_capacity``): the rows of a block, in order,
  form groups of ``moe_group_rows``, and an expert keeps at most ``C =
  ceil(moe_capacity_factor * group * num_experts_per_tok /
  router_num_experts)`` of a group's picks, the first in row order.

**Loss**: mean over the ``b (s - 1)`` positions of ``logsumexp(logits_t) -
logits_t[ids_{t+1}]``, over the sliced vocabulary.

What keeps it inside one chip at 8192 rows: each block is recomputed in the
backward pass (``jax.checkpoint``); the recurrence in blocks of
``TOKEN_BLOCK`` tokens, each recomputed in turn; attention in blocks of
heads and queries; the experts one at a time; the head in blocks of rows.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common
# the same published router (sigmoid scores, a selection bias, a scale, the
# load bound), dense causal attention in blocks and the head's loss in
# blocks as ``kanana2_30b_a3b``'s reference has them, from the same keys
from .kanana2 import causal_attention, head_loss, held_gates

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: tokens per checkpointed block of the recurrence
TOKEN_BLOCK = 256


def kinds(cfg: dict) -> str:
    """The kinds of the blocks held here: the first ``num_hidden_layers``
    entries of the published pattern."""
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    if len(pattern) != cfg["num_hidden_layers"] \
            or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"{cfg['num_hidden_layers']} blocks out of the "
                         f"pattern {cfg['hybrid_override_pattern']!r}")
    return pattern


def mamba_dims(cfg: dict):
    """``(d_inner, the convolution's width)``."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return inner, inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    d, heads = cfg["hidden_size"], cfg["mamba_num_heads"]
    inner, conv_dim = mamba_dims(cfg)
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed_tokens/embedding": (cfg["vocab_size"], d),
              "norm/weight": (d,), "lm_head": (d, cfg["vocab_size"])}
    for i, kind in enumerate(kinds(cfg)):
        shapes[f"layers_{i}/norm/weight"] = (d,)
        m = f"layers_{i}/mixer"
        if kind == MAMBA:
            shapes[f"{m}/in_proj/kernel"] = (d, inner + conv_dim + heads)
            shapes[f"{m}/conv1d"] = (cfg["conv_kernel"], conv_dim)
            shapes[f"{m}/conv_bias"] = (conv_dim,)
            shapes[f"{m}/dt_bias"] = (heads,)
            shapes[f"{m}/A_log"] = (heads,)
            shapes[f"{m}/D"] = (heads,)
            shapes[f"{m}/norm"] = (inner,)
            shapes[f"{m}/out_proj/kernel"] = (inner, d)
        elif kind == ATTENTION:
            shapes[f"{m}/q_proj/kernel"] = (d, h * hd)
            shapes[f"{m}/k_proj/kernel"] = (d, kv * hd)
            shapes[f"{m}/v_proj/kernel"] = (d, kv * hd)
            shapes[f"{m}/o_proj/kernel"] = (h * hd, d)
        else:
            shapes[f"{m}/gate"] = (d, cfg["router_num_experts"])
            shapes[f"{m}/experts_up_proj"] = (held, d, f)
            shapes[f"{m}/experts_down_proj"] = (held, f, d)
            shapes[f"{m}/shared_experts_up_proj/kernel"] = (d, fs)
            shapes[f"{m}/shared_experts_down_proj/kernel"] = (fs, d)
    return shapes


def seeded_dt_bias(cfg: dict, key, heads: int):
    """The inverse softplus of ``dt`` drawn log-uniform on
    ``[time_step_min, time_step_max]`` and floored at ``time_step_floor``
    (the source's own rule: ``dt + log(-expm1(-dt))``)."""
    low, high = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    dt = jnp.exp(jax.random.uniform(key, (heads,), jnp.float32)
                 * (high - low) + low)
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def seeded_weights(cfg: dict, seed: int) -> dict:
    """By the source's own rules where the configuration gives them
    (``A_log = log(1..heads)``, ``D`` one, ``dt_bias`` by
    :func:`seeded_dt_bias`, the convolution's bias zero, norm weights one)
    and normal(0, ``initializer_range``) for every matrix, the table and
    the convolution's taps.  Flat, ``{leaf name: array}``."""
    std = cfg["initializer_range"]

    def rule(name, shape):
        if name.endswith(("/weight", "/mixer/norm", "/D")):
            return ("ones",)
        if name.endswith(("/conv_bias", "/dt_bias", "/A_log")):
            return ("zeros",)
        return ("normal", std)

    flat = dict(common.seeded_params(param_shapes(cfg), rule, seed))
    heads = cfg["mamba_num_heads"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) + 2)
    for i, kind in enumerate(kinds(cfg)):
        if kind == MAMBA:
            m = f"layers_{i}/mixer"
            flat[f"{m}/A_log"] = jnp.log(
                jnp.arange(1, heads + 1, dtype=jnp.float32))
            flat[f"{m}/dt_bias"] = seeded_dt_bias(
                cfg, jax.random.fold_in(key, i), heads)
    return flat


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _causal_conv(x, kernel):
    """Depthwise, ``kernel`` ``[taps, c]``, left-padded by ``taps - 1``."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0
    for j in range(taps):
        out = out + padded[:, j:j + s] * kernel[j]
    return out


def state_recurrence(xh, dt, rate, bh, ch, skip, q,
                     token_block: int = TOKEN_BLOCK):
    """``xh``: ``[b, s, H, P]``; ``dt``: ``[b, s, H]``; ``rate``, ``skip``:
    ``[H]``; ``bh``, ``ch``: ``[b, s, H, N]`` (a group's ``B`` and ``C``
    given to each of its heads).  Returns ``y`` ``[b, s, H, P]``.  The
    products with the state take their operands through ``q`` (the
    control's rounding)."""
    b, s, h, p = xh.shape
    n = bh.shape[-1]
    token_block = min(token_block, s)
    pad = -s % token_block
    k = (s + pad) // token_block

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, k, token_block, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 1)  # [k, T, b, ...]

    def token(state, row):
        x_t, dt_t, b_t, c_t = row
        state = state * jnp.exp(dt_t * rate)[..., None, None] \
            + q(dt_t[..., None] * x_t)[..., :, None] * q(b_t)[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", q(state), q(c_t)) \
            + skip[:, None] * x_t

    def block(state, rows):
        return jax.lax.scan(token, state, rows)

    _, y = jax.lax.scan(jax.checkpoint(block),
                        jnp.zeros((b, h, p, n), jnp.float32),
                        tuple(blocks(x) for x in (xh, dt, bh, ch)))
    y = y.reshape(k * token_block, b, h, p)[:s]   # padded rows: dt = 0
    return jnp.moveaxis(y, 0, 1)


def _mamba(u, p, cfg, q):
    b, s, _ = u.shape
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, conv_dim = mamba_dims(cfg)
    zxbcdt = q(u) @ q(p["in_proj"]["kernel"])
    z = zxbcdt[..., :inner]
    xbc = _silu(_causal_conv(q(zxbcdt[..., inner:inner + conv_dim]),
                             q(p["conv1d"])) + p["conv_bias"])
    dt = jax.nn.softplus(zxbcdt[..., inner + conv_dim:] + p["dt_bias"])
    xh = xbc[..., :inner].reshape(b, s, heads, hp)
    bh, ch = (jnp.repeat(t.reshape(b, s, groups, n), heads // groups, axis=2)
              for t in (xbc[..., inner:inner + groups * n],
                        xbc[..., inner + groups * n:]))
    y = state_recurrence(xh, dt, -jnp.exp(p["A_log"]), bh, ch, p["D"], q)
    y = y.reshape(b, s, inner) * _silu(z)
    y = y.reshape(b, s, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg["norm_eps"])
    return q(y.reshape(b, s, inner) * p["norm"]) @ q(p["out_proj"]["kernel"])


def _attention(x, p, cfg, q):
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    xq = q(x)
    qh = (xq @ q(p["q_proj"]["kernel"])).reshape(b, s, h, hd)
    kh, vh = (jnp.repeat((xq @ q(p[name]["kernel"])).reshape(b, s, kv, hd),
                         h // kv, axis=2) for name in ("k_proj", "v_proj"))
    o = causal_attention(qh, kh, vh, q)
    return q(o.reshape(b, s, h * hd)) @ q(p["o_proj"]["kernel"])


def _relu2(xq, up, down, q):
    return q(jnp.square(jax.nn.relu(xq @ q(up)))) @ q(down)


def moe(x, p, cfg, q, shared: bool = True):
    """The held experts' part of the block plus, with ``shared``, the
    shared expert's; ``x``: ``[b, s, d]``."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    gates = held_gates(flat, p["gate"], cfg)
    xq = q(flat)

    def expert(args):
        up_w, down_w, weight = args
        return weight[:, None] * _relu2(xq, up_w, down_w, q)

    out = jnp.sum(jax.lax.map(jax.checkpoint(expert), (
        p["experts_up_proj"], p["experts_down_proj"], gates.T)), axis=0)
    if shared:
        out = out + _relu2(xq, *(p[f"shared_experts_{k}_proj"]["kernel"]
                                 for k in ("up", "down")), q)
    return out.reshape(b, s, d)


MIXERS = {MAMBA: _mamba, ATTENTION: _attention, EXPERTS: moe}


def _block(x, p, cfg, kind: str, q):
    return x + MIXERS[kind](_norm(x, p["norm"]["weight"], cfg["norm_eps"]),
                            p["mixer"], cfg, q)


def hidden_fn(cfg: dict, q):
    """``hidden(params, ids)``: ``[b, s, d]`` after the final norm; ``q``
    rounds the products' operands."""
    def hidden(params, ids):
        x = params["embed_tokens"]["embedding"][ids]
        for i, kind in enumerate(kinds(cfg)):
            x = jax.checkpoint(
                lambda x, p, kind=kind: _block(x, p, cfg, kind, q))(
                    x, params[f"layers_{i}"])
        return _norm(x, params["norm"]["weight"], cfg["norm_eps"])

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
