"""LFM2-style hybrid decoder (flax/linen), TPU-first: the ``lfm2_moe`` layer
— a sequence operator that is a **gated short convolution** in most layers
and grouped-query softmax attention in the others, then a feed-forward part
that is a dense SwiGLU in the leading layers and a mixture of many small
experts routed by sigmoid scores with a selection bias in the rest — with
the table tied to the head.

Layer ``i`` of operator kind ``o_i`` and feed-forward kind ``f_i``: ``h = x
+ operator_{o_i}(norm(x))``, ``out = h + ff_{f_i}(norm(h))``; after the last
layer a final norm and ``logits = h @ table^T`` in float32.  RMSNorm is ``x
/ rms(x) * w`` with a plain weight that starts at one.  No bias in any
projection or convolution, no dropout.  The two kinds are independent: any
operator stands before either feed-forward part.

* ``"conv"`` (:class:`ShortConv`): ``[B | C | x] = in_proj(u)``, three
  blocks of ``hidden_size`` columns in that order; ``z_t = sum_j w_j (B *
  x)_{t - (taps - 1) + j}`` a channel, zeros before the sequence's start
  (``qwen3_next.causal_depthwise_conv``); ``y = out_proj(C * z)``.  No
  activation, and no state beyond the ``taps - 1`` previous rows.
* ``"full_attention"`` (:class:`Attention`): q to ``num_heads`` heads of
  ``head_dim``, k and v to ``num_kv_heads``; RMSNorm over each head's dims
  on q and on k (one weight of ``head_dim`` each, starting at
  ``qk_norm_init``) **before** the rotary; rotary embedding over all of a
  head's dims, halves paired (``rotate_half``), positions ``0 .. s - 1``;
  causal softmax through the Pallas flash kernels, which take k and v at
  their own head count (a kv head serves its group of q heads by the
  kernels' index maps); ``out_proj``.
* ``"dense"``: ``kanana2.DenseMlp``, a SwiGLU of ``intermediate_size``.
* ``"experts"`` (:class:`SigmoidRoutedMoe`):
  ``parallel/moe.grouped_routed_experts`` over the experts held here
  (``num_experts`` of the router's ``router_experts``, from
  ``first_expert``) under ``moe.route_sigmoid_top_k``: sigmoid scores in
  float32, the ``num_experts_per_tok`` largest of ``score + bias``, the
  scores themselves as weights, divided by the picks' sum plus 1e-6
  (:data:`ROUTE_EPS`, this family's) and multiplied by
  ``routed_scaling_factor``.  No shared expert.  ``selection_bias`` is a
  constant of the module (zeros unless given), never a parameter.  With
  ``moe_capacity_factor`` the load is bounded as GShard bounds it, a group
  of ``moe_group_rows`` rows at a time (``models/sdar.py`` has the same).

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each decoder layer in the backward pass
(``models/recompute.recomputed``): the flash forward kernel's output and
row statistics are always kept, and of the other outputs a second run would
make again what fits the byte budget ``recompute`` reckons from the device's
memory and the shapes (:meth:`Lfm2.recompute_parts`), in rank order: the
router's logits, picks and order, ``C * z`` (the convolution's
``out_proj``'s operand), either operator's ``out_proj`` output, ``q_proj``'s,
q as the kernels take it, the dense SwiGLU's gate and up, the convolution's
``in_proj`` output, ``k_proj`` / ``v_proj``'s, k and v as the kernels take
them.  Device scopes (``models/scopes.py``, docs/profiling.md):
``hvd_sconv`` (``hvd_sconv_in``, ``hvd_sconv_conv``, ``hvd_sconv_out``),
``hvd_attn`` (``hvd_attn_qkv``, the kernels' own, ``hvd_attn_out``),
``hvd_dense_mlp``, ``hvd_moe`` (``hvd_moe_route``, ``hvd_moe_experts``),
``hvd_head``; counter ``hvd_sconv_layers_traced_total{taps,channels}``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from .. import metrics
from ..ops import flash_attention as flash
from ..ops.flash_attention import flash_attention
from ..parallel import moe
from ..parallel.moe import grouped_routed_experts, route_sigmoid_top_k
from . import scopes
from .kanana2 import DenseMlp
from .qwen3_next import (_dense, _normal, apply_rotary,
                         causal_depthwise_conv, rotary_tables)
from .recompute import recomputed
from .sdar import RMSNorm

_F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
#: the published ``layer_types`` of LFM2-24B-A2B: ``conv, conv,
#: full_attention, conv`` ten times over (30 ``conv``, 10
#: ``full_attention``)
LAYER_TYPES = (CONV, CONV, ATTENTION, CONV) * 10
#: what ``lfm2_moe`` adds to the picks' sum before it divides by it
ROUTE_EPS = 1e-6


class ShortConv(nn.Module):
    """The gated short convolution over ``[b, s, d]``: gated going in (``B
    * x``) and coming out (``C * z``), ``taps`` causal taps a channel
    between."""
    taps: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        metrics.record_sconv_layer(self.taps, d)
        with jax.named_scope(scopes.SCONV):
            with jax.named_scope(scopes.SCONV_IN):
                bcx = checkpoint_name(_dense(3 * d, "in_proj", self)(u),
                                      scopes.KEEP_SCONV_IN_PROJ)
                gate_in, gate_out, x = (bcx[..., i * d:(i + 1) * d]
                                        for i in range(3))
            with jax.named_scope(scopes.SCONV_CONV):
                kernel = self.param("conv", _normal(), (self.taps, d),
                                    self.param_dtype)
                z = causal_depthwise_conv(gate_in * x,
                                          kernel.astype(self.dtype))
                y = checkpoint_name(gate_out * z, scopes.KEEP_SCONV_GATE)
            with jax.named_scope(scopes.SCONV_OUT):
                return checkpoint_name(_dense(d, "out_proj", self)(y),
                                       scopes.KEEP_OUT_PROJ)


class Attention(nn.Module):
    """Grouped-query causal attention with per-head q / k norms before a
    full rotary embedding."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    qk_norm_init: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope(scopes.ATTN):
            with jax.named_scope(scopes.ATTN_QKV):
                q = checkpoint_name(_dense(h * hd, "q_proj", self)(x),
                                    scopes.KEEP_Q_PROJ).reshape(b, s, h, hd)
                k, v = (checkpoint_name(
                    _dense(kv * hd, name, self)(x),
                    scopes.KEEP_KV_PROJ).reshape(b, s, kv, hd)
                    for name in ("k_proj", "v_proj"))
                # the projections' scale cancels in these norms: their
                # weights are the softmax's temperature (models/sdar.py)
                norm = dict(eps=self.eps, init=self.qk_norm_init,
                            dtype=self.dtype, param_dtype=self.param_dtype)
                q = RMSNorm(name="q_layernorm", **norm)(q)
                k = RMSNorm(name="k_layernorm", **norm)(k)
                cos, sin = rotary_tables(jnp.arange(s), hd, self.rope_theta)
                q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            o = flash_attention(q, k, v, causal=True)
            with jax.named_scope(scopes.ATTN_OUT):
                return checkpoint_name(
                    _dense(d, "out_proj", self)(o.reshape(b, s, h * hd)),
                    scopes.KEEP_OUT_PROJ)


class SigmoidRoutedMoe(nn.Module):
    """The experts held here of ``router_experts``, ``top_k`` a token by
    sigmoid scores and a selection bias; no shared expert.  With a
    ``capacity_factor`` the rows are taken in groups of ``group_rows`` and
    an expert takes at most ``capacity_factor * group * top_k /
    router_experts`` rows of a group."""
    num_experts: int          # held here
    router_experts: int       # the router's width: all the layer's experts
    first_expert: int
    top_k: int
    expert_dim: int
    scale: float
    selection_bias: Optional[Sequence[float]] = None
    group_rows: Optional[int] = None
    capacity_factor: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        with jax.named_scope(scopes.MOE):
            router = self.param("gate", _normal(), (d, self.router_experts),
                                self.param_dtype)
            shapes = {"gate_proj": (self.num_experts, d, self.expert_dim),
                      "up_proj": (self.num_experts, d, self.expert_dim),
                      "down_proj": (self.num_experts, self.expert_dim, d)}
            experts = {name: self.param(f"experts_{name}", _normal(), shape,
                                        self.param_dtype)
                       for name, shape in shapes.items()}
            bias = jnp.zeros((self.router_experts,), _F32) \
                if self.selection_bias is None \
                else jnp.asarray(self.selection_bias, _F32)
            return grouped_routed_experts(
                x, router, experts, top_k=self.top_k,
                first_expert=self.first_expert, group_rows=self.group_rows,
                capacity_factor=self.capacity_factor,
                route=functools.partial(route_sigmoid_top_k, bias=bias,
                                        scale=self.scale, eps=ROUTE_EPS))


#: {an operator's kind: (its module, its name in a layer)}
OPERATORS = {CONV: (ShortConv, "conv"), ATTENTION: (Attention, "self_attn")}


class DecoderLayer(nn.Module):
    """One operator of kind ``operator`` and one feed-forward part, each
    behind its norm and before its residual add."""
    operator: str             # CONV or ATTENTION
    operator_args: dict
    moe: Optional[dict]       # None: a dense layer of ``dense_width``
    dense_width: int
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        operator_cls, name = OPERATORS[self.operator]
        h = RMSNorm(self.eps, name="operator_norm", **kw)(x)
        x = x + operator_cls(name=name, **self.operator_args, **kw)(h)
        h = RMSNorm(self.eps, name="ffn_norm", **kw)(x)
        if self.moe is None:
            return x + DenseMlp(self.dense_width, name="feed_forward",
                                **kw)(h)
        return x + SigmoidRoutedMoe(name="feed_forward", **self.moe,
                                    **kw)(h)


class Lfm2(nn.Module):
    """Token ids ``[b, s]`` -> logits ``[b, s, vocab_size]`` float32.

    The defaults are the published widths of LFM2-24B-A2B; the layers'
    operator kinds (so the depth), the leading dense layers, the experts
    held here and the vocabulary are what a caller sizes."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Sequence[str] = LAYER_TYPES
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    conv_taps: int = 3
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    num_experts: int = 64             # held here
    router_experts: int = 64          # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    selection_bias: Optional[Sequence[float]] = None
    moe_group_rows: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    qk_norm_init: float = 1.0
    norm_eps: float = 1e-5
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def kinds(self) -> tuple:
        """Each layer's operator kind."""
        kinds = tuple(self.layer_types)
        if not kinds or set(kinds) - set(OPERATORS):
            raise ValueError(f"a layer's operator is one of "
                             f"{sorted(OPERATORS)}, not {self.layer_types!r}")
        return kinds

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` ids: the bytes each name would keep over the layers that have
        it, and the activations the step holds whatever is kept (the
        layers' inputs, the flash kernels' residuals, the logits)."""
        rows, size = b * s, jnp.dtype(self.dtype).itemsize
        kinds = self.kinds()
        layers, n_conv, n_attn = (len(kinds), kinds.count(CONV),
                                  kinds.count(ATTENTION))
        dense = min(self.num_dense_layers, layers)
        d, q = self.hidden_size, self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        parts = {
            moe.ROUTING: (layers - dense) * moe.routing_bytes(
                rows, self.router_experts, self.num_experts_per_tok),
            scopes.KEEP_SCONV_GATE: n_conv * rows * d * size,
            scopes.KEEP_OUT_PROJ: layers * rows * d * size,
            scopes.KEEP_Q_PROJ: n_attn * rows * q * size,
            flash.FLASH_Q: n_attn * rows * q * size,
            scopes.KEEP_MLP: dense * rows * 2 * self.intermediate_size
            * size,
            scopes.KEEP_SCONV_IN_PROJ: n_conv * rows * 3 * d * size,
            scopes.KEEP_KV_PROJ: n_attn * rows * 2 * kv * size,
            flash.FLASH_K: n_attn * rows * kv * size,
            flash.FLASH_V: n_attn * rows * kv * size,
        }
        held = (layers * rows * d * size
                + n_attn * flash.residual_bytes(b, self.num_heads, s,
                                                self.head_dim, size)
                + rows * self.vocab_size * 4)
        return parts, held

    @nn.compact
    def __call__(self, ids):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         embedding_init=_normal(), name="embed_tokens", **kw)
        x = embed(ids)
        layer_cls = DecoderLayer
        if self.remat:
            layer_cls = recomputed(
                DecoderLayer, self, *self.recompute_parts(*ids.shape))
        operators = {
            CONV: dict(taps=self.conv_taps),
            ATTENTION: dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta,
                eps=self.norm_eps, qk_norm_init=self.qk_norm_init)}
        experts = dict(
            num_experts=self.num_experts,
            router_experts=self.router_experts,
            first_expert=self.first_expert, top_k=self.num_experts_per_tok,
            expert_dim=self.moe_intermediate_size,
            scale=self.routed_scaling_factor,
            selection_bias=self.selection_bias,
            group_rows=self.moe_group_rows,
            capacity_factor=self.moe_capacity_factor)
        for i, kind in enumerate(self.kinds()):
            x = layer_cls(
                operator=kind, operator_args=operators[kind],
                moe=None if i < self.num_dense_layers else experts,
                dense_width=self.intermediate_size, eps=self.norm_eps,
                name=f"layers_{i}", **kw)(x)
        with jax.named_scope(scopes.HEAD):
            x = RMSNorm(self.norm_eps, name="embedding_norm", **kw)(x)
            # the head is the table: logits = x @ table^T, float32 for the
            # softmax
            return jnp.einsum("bsd,vd->bsv", x,
                              embed.embedding.astype(self.dtype),
                              preferred_element_type=_F32)


def lfm2_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: the published
    layers 1 to 5 (a dense ``conv`` layer, then ``full_attention, conv,
    conv, conv`` with experts), four of eight experts held, two a token."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, layer_types=LAYER_TYPES[1:6],
            num_dense_layers=1, intermediate_size=96, num_heads=4,
            num_kv_heads=2, head_dim=16, rope_theta=1e4, num_experts=4,
            router_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32).items():
        kw.setdefault(key, value)
    return Lfm2(**kw)
