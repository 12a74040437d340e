"""Mellum-2-style decoder (flax/linen), TPU-first: grouped-query softmax
attention whose layers differ by *kind* — most see a sliding window of
keys, every fourth sees them all — with rotary parameters of each kind's
own (YaRN on the full layers), and in every layer a top-k mixture of many
small experts with no shared expert; an untied head.

Layer ``i`` is of kind ``layer_types[i]`` (by default three
``sliding_attention`` then one ``full_attention``, repeated).  Every layer:
``h = x + attn(norm(x))``, ``out = h + moe(norm(h))``; RMSNorm is ``x /
rms(x) * w`` with a plain weight that starts at one.  No bias anywhere, no
per-head norm, no attention sink.

* Attention (:class:`Attention`): ``q_proj`` / ``k_proj`` / ``v_proj`` to
  ``num_heads`` / ``num_kv_heads`` heads of ``head_dim``; rotary embedding
  over all of a head's dims, halves paired (``rotate_half``), from the
  table of the layer's kind; the Pallas flash kernels under the kind's
  ``Mask`` — ``ops/flash_attention.sliding_window_mask(sliding_window)``
  (row ``i`` sees keys ``j`` with ``i - sliding_window < j <= i``) or the
  causal one — which take k and v at their ``num_kv_heads`` heads (a kv
  head serves its group of q heads by the kernels' index maps);
  ``o_proj``.
* Rotary tables (:func:`rotary_frequencies`, :func:`rotary_table`): both in
  float32, made once a step outside the layers and handed to each layer by
  its kind.  The window layers rotate by ``position * rope_theta ** (-2c /
  head_dim)``.  The full layers' frequencies are YaRN's (``yarn_factor``
  over ``yarn_original_positions``: the slow pairs interpolated, the fast
  ones as they were, a linear ramp between ``yarn_beta_fast`` and
  ``yarn_beta_slow`` rotations) and their table carries
  ``yarn_attention_factor`` on cos and on sin, so a full layer's scores
  carry its square.  ``yarn_factor=None``: the full layers take the window
  layers' table.
* Expert layer: ``models/sdar.RoutedMoe`` over the experts held here
  (``num_experts`` of the router's ``router_experts``, from
  ``first_expert``): softmax over all the router's outputs in float32, the
  ``num_experts_per_tok`` largest, their weights divided by their sum;
  with ``moe_capacity_factor`` the load is bounded a group of
  ``moe_group_rows`` rows at a time.

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each decoder layer in the backward pass
(``models/recompute.recomputed``), layers of both kinds under the one
policy: the flash forward kernel's output and row statistics are always
kept, and of the other outputs a second run would make again what fits the
byte budget reckoned from the device's memory and the shapes
(:meth:`Mellum2.recompute_parts`), in rank order: the router's logits,
picks and order, ``o_proj``'s output, ``q_proj``'s, q as the kernels take
it, ``k_proj`` / ``v_proj``'s, k and v as the kernels take them.  Device
scopes (``models/scopes.py``, docs/profiling.md): ``hvd_rotary_tables``;
``hvd_attn`` and inside it the layer's kind, ``hvd_attn_window`` or
``hvd_attn_full``, round the whole of its attention (``hvd_attn_qkv``, the
kernels' own, ``hvd_attn_out``); ``hvd_moe`` (``hvd_moe_route``,
``hvd_moe_experts``); ``hvd_head``.  Counter
``hvd_attn_layers_traced_total{kind,window,rope}``.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from .. import metrics
from ..ops import flash_attention as flash
from ..ops.flash_attention import flash_attention, sliding_window_mask
from ..parallel import moe
from . import scopes
from .qwen3_next import _dense, _normal, apply_rotary, lm_head
from .recompute import recomputed
from .sdar import RMSNorm, RoutedMoe

_F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
#: a layer kind's scope inside ``hvd_attn``
KIND_SCOPES = {SLIDING: scopes.ATTN_WINDOW, FULL: scopes.ATTN_FULL}


def yarn_correction_range(head_dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """``(low, high)``: the pairs (of ``head_dim // 2``) between which
    YaRN's ramp runs.  Pair ``c`` turns ``original * theta ** (-2c /
    head_dim) / (2 pi)`` times over the original context; the pair that
    turns ``r`` times is ``head_dim ln(original / (2 pi r)) / (2 ln
    theta)``, rounded outwards (``truncate``, the default) and kept inside
    the head."""
    def pair(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), head_dim - 1))


def rotary_frequencies(head_dim: int, theta: float, *,
                       yarn_factor: Optional[float] = None,
                       original: int = 0, beta_fast: float = 32.0,
                       beta_slow: float = 1.0):
    """``[head_dim // 2]`` float32 angles a position.  Without a
    ``yarn_factor`` ``theta ** (-2c / head_dim)``; with one, YaRN's: the
    pairs below ``low`` as they are (they turn often enough inside the
    original context), those past ``high`` divided by the factor, a linear
    ramp between."""
    plain = theta ** (-jnp.arange(0, head_dim, 2, dtype=_F32) / head_dim)
    if yarn_factor is None:
        return plain
    low, high = yarn_correction_range(head_dim, theta, original, beta_fast,
                                      beta_slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=_F32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / yarn_factor * ramp + plain * (1.0 - ramp)


def rotary_table(positions, inv_freq, factor: float = 1.0):
    """``(cos, sin)``, each ``[s, 2 len(inv_freq)]`` float32, times
    ``factor``: the frequencies twice over, for halves paired as in
    ``rotate_half`` (what ``apply_rotary`` takes)."""
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return factor * jnp.cos(angles), factor * jnp.sin(angles)


class Attention(nn.Module):
    """Grouped-query attention of one kind of layer: a window layer where
    ``window`` is set, a full (causal) one where it is 0; its rotary table
    is the caller's."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int               # keys a row sees; 0: all before it
    rope: str                 # the kind's rotary rule, for the counter
    q_init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x, cos, sin):
        b, s, d = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        kind = SLIDING if self.window else FULL
        metrics.record_attn_layer(kind, self.window, self.rope)
        with jax.named_scope(scopes.ATTN), \
                jax.named_scope(KIND_SCOPES[kind]):
            with jax.named_scope(scopes.ATTN_QKV):
                q = checkpoint_name(nn.Dense(
                    h * hd, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=_normal(self.q_init_std), name="q_proj")(x),
                    scopes.KEEP_Q_PROJ).reshape(b, s, h, hd)
                k, v = (checkpoint_name(
                    _dense(kv * hd, name, self)(x),
                    scopes.KEEP_KV_PROJ).reshape(b, s, kv, hd)
                    for name in ("k_proj", "v_proj"))
                q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            o = flash_attention(
                q, k, v, mask=sliding_window_mask(self.window)
                if self.window else flash.CAUSAL)
            with jax.named_scope(scopes.ATTN_OUT):
                return checkpoint_name(
                    _dense(d, "o_proj", self)(o.reshape(b, s, h * hd)),
                    scopes.KEEP_OUT_PROJ)


class DecoderLayer(nn.Module):
    attention: dict
    moe: dict
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x, cos, sin):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = RMSNorm(self.eps, name="input_layernorm", **kw)(x)
        x = x + Attention(name="self_attn", **self.attention, **kw)(
            h, cos, sin)
        h = RMSNorm(self.eps, name="post_attention_layernorm", **kw)(x)
        return x + RoutedMoe(name="mlp", **self.moe, **kw)(h)


class Mellum2(nn.Module):
    """Token ids ``[b, s]`` -> logits ``[b, s, vocab_size]`` float32.

    The defaults are the published widths of Mellum2-12B-A2.5B-Instruct;
    depth, the experts held here and the vocabulary are what a caller
    sizes.  ``layer_types`` names each layer's kind; ``None`` is the
    published pattern, three window layers then a full one."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    layer_types: Optional[Sequence[str]] = None
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn_factor: Optional[float] = 16.0
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    num_experts: int = 64             # held here
    router_experts: int = 64          # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    moe_group_rows: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    q_init_std: float = 0.02
    rms_norm_eps: float = 1e-6
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def kinds(self) -> tuple:
        """Each layer's kind."""
        kinds = tuple(self.layer_types) if self.layer_types is not None \
            else tuple(FULL if i % 4 == 3 else SLIDING
                       for i in range(self.num_layers))
        if len(kinds) != self.num_layers or set(kinds) - set(KIND_SCOPES):
            raise ValueError(
                f"{self.num_layers} layers want as many kinds out of "
                f"{sorted(KIND_SCOPES)}, not {kinds}")
        return kinds

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` ids: the bytes each name would keep over the layers (a window
        layer keeps what a full one keeps), and the activations the step
        holds whatever is kept (the layers' inputs, the flash kernels'
        residuals, the logits)."""
        rows, size = b * s, jnp.dtype(self.dtype).itemsize
        d, q = self.hidden_size, self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        parts = {name: self.num_layers * n for name, n in {
            moe.ROUTING: moe.routing_bytes(
                rows, self.router_experts, self.num_experts_per_tok),
            scopes.KEEP_OUT_PROJ: rows * d * size,
            scopes.KEEP_Q_PROJ: rows * q * size,
            scopes.KEEP_KV_PROJ: rows * 2 * kv * size,
            flash.FLASH_Q: rows * q * size,
            flash.FLASH_K: rows * kv * size,
            flash.FLASH_V: rows * kv * size,
        }.items()}
        held = (self.num_layers * (rows * d * size + flash.residual_bytes(
                    b, self.num_heads, s, self.head_dim, size))
                + rows * self.vocab_size * 4)
        return parts, held

    def rotary_tables(self, positions) -> dict:
        """``{kind: (cos, sin)}`` for the kinds the model has."""
        plain = (rotary_frequencies(self.head_dim, self.rope_theta), 1.0)
        yarn = plain if self.yarn_factor is None else (rotary_frequencies(
            self.head_dim, self.rope_theta, yarn_factor=self.yarn_factor,
            original=self.yarn_original_positions,
            beta_fast=self.yarn_beta_fast, beta_slow=self.yarn_beta_slow),
            self.yarn_attention_factor)
        # sorted: a set of strings iterates by the process's hash seed, and
        # the order of the two tables is part of the step's program and of
        # its compile-cache key
        return {kind: rotary_table(positions, *(
            yarn if kind == FULL else plain))
            for kind in sorted(set(self.kinds()))}

    @nn.compact
    def __call__(self, ids):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        with jax.named_scope(scopes.ROTARY_TABLES):
            tables = self.rotary_tables(jnp.arange(ids.shape[1]))
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     embedding_init=_normal(), name="embed_tokens",
                     **kw)(ids)
        layer_cls = DecoderLayer
        if self.remat:
            layer_cls = recomputed(
                DecoderLayer, self, *self.recompute_parts(*ids.shape))
        experts = dict(
            num_experts=self.num_experts,
            router_experts=self.router_experts,
            first_expert=self.first_expert, top_k=self.num_experts_per_tok,
            expert_dim=self.moe_intermediate_size,
            group_rows=self.moe_group_rows,
            capacity_factor=self.moe_capacity_factor)
        yarn = self.yarn_factor is not None
        for i, kind in enumerate(self.kinds()):
            attention = dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim,
                window=self.sliding_window if kind == SLIDING else 0,
                rope="yarn" if yarn and kind == FULL else "default",
                q_init_std=self.q_init_std)
            x = layer_cls(attention=attention, moe=experts,
                          eps=self.rms_norm_eps, name=f"layers_{i}",
                          **kw)(x, *tables[kind])
        return lm_head(self, x, self.rms_norm_eps, RMSNorm)


def mellum2_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: one period of
    four layers under a window of 16, four of eight experts held."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, sliding_window=16, rope_theta=1e4,
            yarn_factor=4.0, yarn_original_positions=32, num_experts=4,
            router_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32).items():
        kw.setdefault(key, value)
    return Mellum2(**kw)
