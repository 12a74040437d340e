"""GPT-2 (Radford et al. 2019) in plain float32 ``jax.numpy``: forward,
next-token loss and, through ``jax.grad``, the gradient.

Pre-LN decoder blocks, learned positions, tanh GELU, tied output head, as
the paper and the Hugging Face ``gpt2`` config describe them.  Departures
from the published model, each because the program under test makes it:
LayerNorm's epsilon and the number of position rows come from the
configuration file (the program's are 1e-6 and ``max(sequence, 1024)``).

Long sequences are taken in blocks so the float32 intermediates fit one
chip: attention in blocks of queries against all keys, the output head in
blocks of tokens, each recomputed in the backward pass.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common

#: queries per attention block and tokens per output-head block
QUERY_BLOCK = 1024
TOKEN_BLOCK = 2048


def param_shapes(cfg: dict, positions: int) -> Dict[str, tuple]:
    d, h, m = cfg["n_embd"], cfg["n_head"], cfg["n_inner"]
    hd = d // h
    shapes = {
        "wte/embedding": (cfg["vocab_size"], d),
        "wpe/embedding": (positions, d),
        "LayerNorm_0/scale": (d,),
        "LayerNorm_0/bias": (d,),
    }
    for i in range(cfg["n_layer"]):
        p = f"EncoderLayer_{i}"
        for ln in ("LayerNorm_0", "LayerNorm_1"):
            shapes[f"{p}/{ln}/scale"] = (d,)
            shapes[f"{p}/{ln}/bias"] = (d,)
        for name in ("query", "key", "value"):
            shapes[f"{p}/SelfAttention_0/{name}/kernel"] = (d, h, hd)
            shapes[f"{p}/SelfAttention_0/{name}/bias"] = (h, hd)
        shapes[f"{p}/SelfAttention_0/out/kernel"] = (h, hd, d)
        shapes[f"{p}/SelfAttention_0/out/bias"] = (d,)
        shapes[f"{p}/Dense_0/kernel"] = (d, m)
        shapes[f"{p}/Dense_0/bias"] = (m,)
        shapes[f"{p}/Dense_1/kernel"] = (m, d)
        shapes[f"{p}/Dense_1/bias"] = (d,)
    return shapes


def seeded_weights(cfg: dict, positions: int, seed: int) -> dict:
    """GPT-2's own initialisation: normal(0, initializer_range) for every
    matrix and table, ones and zeros for LayerNorm, zero biases.  Flat,
    ``{leaf name: array}``."""
    std = cfg["initializer_range"]

    def rule(name, shape):
        if name.endswith(("kernel", "embedding")):
            return ("normal", std)
        return ("ones",) if name.endswith("scale") else ("zeros",)

    return common.seeded_params(param_shapes(cfg, positions), rule, seed)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(qh, kh, vh, q):
    """Causal softmax attention, ``[b, s, h, hd]`` in and out; queries in
    blocks of QUERY_BLOCK when the sequence is longer than that."""
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
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(probs), vq)

    if s <= QUERY_BLOCK:
        return block((qh, 0))
    if s % QUERY_BLOCK:
        raise ValueError(f"sequence {s} is not a multiple of {QUERY_BLOCK}")
    n = s // QUERY_BLOCK
    blocks = qh.reshape(b, n, QUERY_BLOCK, h, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(jax.checkpoint(block),
                      (blocks, jnp.arange(n) * QUERY_BLOCK))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _layer(x, p, eps, q):
    h = _layer_norm(x, p["LayerNorm_0"], eps)
    a = p["SelfAttention_0"]
    hq = q(h)
    qh, kh, vh = (jnp.einsum("bsd,dhk->bshk", hq, q(a[n]["kernel"]))
                  + a[n]["bias"] for n in ("query", "key", "value"))
    o = _attention(qh, kh, vh, q)
    x = x + jnp.einsum("bshk,hkd->bsd", q(o), q(a["out"]["kernel"])) \
        + a["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"], eps)
    h = _gelu_tanh(q(h) @ q(p["Dense_0"]["kernel"]) + p["Dense_0"]["bias"])
    return x + q(h) @ q(p["Dense_1"]["kernel"]) + p["Dense_1"]["bias"]


def _head_loss(x, table, targets, q):
    """Mean next-token cross-entropy of ``x @ table.T`` against ``targets``,
    TOKEN_BLOCK rows at a time."""
    n, d = x.shape
    pad = -n % TOKEN_BLOCK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    weight = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))
    tq = q(table)

    def block(args):
        xb, tb, wb = args
        logits = q(xb) @ tq.T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * wb)

    k = (n + pad) // TOKEN_BLOCK
    sums = jax.lax.map(jax.checkpoint(block), (
        x.reshape(k, TOKEN_BLOCK, d), targets.reshape(k, TOKEN_BLOCK),
        weight.reshape(k, TOKEN_BLOCK)))
    return jnp.sum(sums) / n


def loss_fn(cfg: dict, precision: str = "float32"):
    """``loss(params, ids)``: mean cross-entropy of predicting
    ``ids[:, t + 1]`` at position ``t``."""
    eps = cfg["layer_norm_epsilon"]
    q = common.operand_rounding(precision)

    def loss(params, ids):
        b, s = ids.shape
        x = params["wte"]["embedding"][ids] \
            + params["wpe"]["embedding"][:s][None]
        # one traced layer scanned over the stacked weights, each layer
        # recomputed in the backward pass: a twelfth of the tracing and
        # compiling of a Python loop, the same arithmetic
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *(params[f"EncoderLayer_{i}"] for i in range(cfg["n_layer"])))
        x, _ = jax.lax.scan(
            lambda x, p: (jax.checkpoint(
                lambda x, p: _layer(x, p, eps, q))(x, p), None),
            x, stacked)
        x = _layer_norm(x, params["LayerNorm_0"], eps)
        d = x.shape[-1]
        return _head_loss(x[:, :-1].reshape(b * (s - 1), d),
                          params["wte"]["embedding"],
                          ids[:, 1:].reshape(b * (s - 1)), q)

    return loss
