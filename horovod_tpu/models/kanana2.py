"""Kanana-2-style latent-attention decoder (flax/linen), TPU-first: the
``deepseek_v3`` layer — multi-head latent attention (a compressed kv with a
separate rotary key, v narrower than q.k), a leading dense SwiGLU layer and
then mixtures of many small experts routed by sigmoid scores with a
selection bias, beside shared experts — with an untied head.

Every layer: ``h = x + attn(norm(x))``, ``out = h + ffn(norm(h))``; RMSNorm
is ``x / rms(x) * w`` with a plain weight that starts at one.  No bias in
any projection.

* Latent attention (:class:`LatentAttention`): ``q_proj`` to ``num_heads``
  heads of ``qk_nope_head_dim + qk_rope_head_dim`` (no q compression);
  ``kv_a_proj_with_mqa`` to the compressed kv ``c`` (``kv_lora_rank``) and
  *one* rotary key for all heads; RMSNorm on ``c``; ``kv_b_proj`` from
  ``c`` to each head's ``[k_nope | v]``; rotary embedding on the last
  ``qk_rope_head_dim`` of each q head and on the shared key, interleaved
  pairs ``(2i, 2i + 1)``; ``k = [k_nope | k_rope]`` a head, materialised in
  HBM (the shared key is repeated to every head: by index map in the
  kernels is a later optimisation); causal softmax attention through the
  Pallas flash kernels, which score q.k at its own head size and weigh v at
  v's; ``o_proj``.  This is the form a model trains in; the absorbed form
  that serves from ``c`` alone is not here.
* Layer ``i < first_dense_layers``: a dense SwiGLU MLP of
  ``intermediate_size``.
* Every other layer: ``parallel/moe.routed_experts`` over the experts held
  here (``num_experts`` of the router's ``router_experts``, from
  ``first_expert``) under ``moe.route_sigmoid_top_k``: sigmoid scores in
  float32, the ``num_experts_per_tok`` largest of ``score + bias``, the
  scores themselves as weights, normalised over the picks and multiplied by
  ``routed_scaling_factor``; plus one SwiGLU of ``num_shared_experts x
  moe_intermediate_size`` every token takes.  ``selection_bias`` is a
  constant of the module (zeros unless given), never a parameter: nothing
  here updates it between steps.  With ``moe_capacity_factor`` the load is
  bounded as GShard bounds it, a group of ``moe_group_rows`` rows at a
  time (``models/sdar.py`` has the same).

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each decoder layer in the backward pass
(``models/recompute.recomputed``): the flash forward kernel's output and
row statistics are always kept, so a layer calls it once a step, and of
the other outputs a second run would make again what fits the byte budget
``recompute`` reckons from the device's memory and the shapes
(:meth:`Kanana2.recompute_parts`), in rank order: the router's logits,
picks and order, ``o_proj``'s output, ``q_proj``'s, q as the kernels take
it (after its rotary part and the swap), the SwiGLUs' gate and up,
``kv_a_proj_with_mqa``'s output, k and v as the kernels take them (with
those kept ``kv_b_proj``'s output is read by nothing in the backward pass
and has no name).  A part kept has no op with ``rematted_computation`` on
its path; counter ``hvd_recompute_kept_bytes_traced_total{name}``.  Device
scopes
(``models/scopes.py``, docs/profiling.md): ``hvd_mla`` (``hvd_mla_q``,
``hvd_mla_latent``, the kernels' own, ``hvd_mla_out``), ``hvd_dense_mlp``,
``hvd_moe`` (``hvd_moe_route``, ``hvd_moe_experts``, ``hvd_moe_shared``),
``hvd_head``; counter ``hvd_mla_layers_traced_total{qk,v,latent}``.
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
from .qwen3_next import _dense, _normal, lm_head
from .recompute import recomputed
from .sdar import RMSNorm

_F32 = jnp.float32


def interleaved_rotary(x, positions, theta: float):
    """``x``: ``[..., s, h, r]`` with ``positions`` ``[s]``; the pairs ``(2i,
    2i + 1)`` of the last dim are rotated by ``positions * theta ** (-2i /
    r)``.  Returns the rotated pairs' first members, then their second
    (``[x_0' x_2' .. | x_1' x_3' ..]``): q and k both come out in that
    order, so their products are those of the pairs rotated in place."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=_F32) / r)
    angles = positions.astype(_F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x = x.astype(_F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class LatentAttention(nn.Module):
    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    eps: float
    q_init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        h, nope, rope, dv = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
        positions = jnp.arange(s)
        metrics.record_mla_layer(nope + rope, dv, self.kv_lora_rank)
        with jax.named_scope(scopes.MLA):
            with jax.named_scope(scopes.MLA_Q):
                q = checkpoint_name(nn.Dense(
                    h * (nope + rope), use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=_normal(self.q_init_std), name="q_proj")(x),
                    scopes.KEEP_Q_PROJ).reshape(b, s, h, nope + rope)
                q = jnp.concatenate([q[..., :nope], interleaved_rotary(
                    q[..., nope:], positions, self.rope_theta).astype(
                        self.dtype)], axis=-1)
            # what latent attention costs beyond a plain k / v projection
            with jax.named_scope(scopes.MLA_LATENT):
                ckr = checkpoint_name(
                    _dense(self.kv_lora_rank + rope, "kv_a_proj_with_mqa",
                           self)(x), scopes.KEEP_KV_PROJ)
                c = RMSNorm(self.eps, name="kv_a_layernorm",
                            dtype=self.dtype, param_dtype=self.param_dtype)(
                                ckr[..., :self.kv_lora_rank])
                kv = _dense(h * (nope + dv), "kv_b_proj", self)(c).reshape(
                    b, s, h, nope + dv)
                # one rotary key, the same for every head
                k_rope = interleaved_rotary(
                    ckr[..., None, self.kv_lora_rank:], positions,
                    self.rope_theta).astype(self.dtype)
                k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                    k_rope, (b, s, h, rope))], axis=-1)
                v = kv[..., nope:]
            o = flash_attention(q, k, v, causal=True)
            with jax.named_scope(scopes.MLA_OUT):
                return checkpoint_name(
                    _dense(d, "o_proj", self)(o.reshape(b, s, h * dv)),
                    scopes.KEEP_OUT_PROJ)


def _swiglu(module: nn.Module, x, width: int, prefix: str = ""):
    gate, up = (checkpoint_name(_dense(width, prefix + name, module)(x),
                                scopes.KEEP_MLP)
                for name in ("gate_proj", "up_proj"))
    return _dense(x.shape[-1], prefix + "down_proj", module)(
        jax.nn.silu(gate) * up)


class DenseMlp(nn.Module):
    width: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(scopes.DENSE_MLP):
            return _swiglu(self, x, self.width)


class SharedRoutedMoe(nn.Module):
    """The experts held here of ``router_experts``, ``top_k`` a token by
    sigmoid scores and a selection bias, plus the shared experts as one
    SwiGLU of ``shared_dim`` that every token takes.  With a
    ``capacity_factor`` the rows are taken in groups of ``group_rows`` and
    an expert takes at most ``capacity_factor * group * top_k /
    router_experts`` rows of a group."""
    num_experts: int          # held here
    router_experts: int       # the router's width: all the layer's experts
    first_expert: int
    top_k: int
    expert_dim: int
    shared_dim: int
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
            routed = grouped_routed_experts(
                x, router, experts, top_k=self.top_k,
                first_expert=self.first_expert, group_rows=self.group_rows,
                capacity_factor=self.capacity_factor,
                route=functools.partial(route_sigmoid_top_k, bias=bias,
                                        scale=self.scale))
            with jax.named_scope(scopes.MOE_SHARED):
                shared = _swiglu(self, x, self.shared_dim, "shared_experts_")
            return routed + shared


class DecoderLayer(nn.Module):
    attention: dict
    moe: Optional[dict]       # None: a dense layer of ``dense_width``
    dense_width: int
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = RMSNorm(self.eps, name="input_layernorm", **kw)(x)
        x = x + LatentAttention(eps=self.eps, name="self_attn",
                                **self.attention, **kw)(h)
        h = RMSNorm(self.eps, name="post_attention_layernorm", **kw)(x)
        if self.moe is None:
            return x + DenseMlp(self.dense_width, name="mlp", **kw)(h)
        return x + SharedRoutedMoe(name="mlp", **self.moe, **kw)(h)


class Kanana2(nn.Module):
    """Token ids ``[b, s]`` -> logits ``[b, s, vocab_size]`` float32.

    The defaults are the published widths of
    kanana-2-30b-a3b-instruct-2601; depth, the experts held here and the
    vocabulary are what a caller sizes."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    first_dense_layers: int = 1
    intermediate_size: int = 6144
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    num_experts: int = 128            # held here
    router_experts: int = 128         # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    num_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    selection_bias: Optional[Sequence[float]] = None
    moe_group_rows: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    q_init_std: float = 0.02
    rms_norm_eps: float = 1e-6
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` ids: the bytes each name would keep over the layers that have
        it, and the activations the step holds whatever is kept (the
        layers' inputs, the flash kernels' residuals, the logits)."""
        rows, size = b * s, jnp.dtype(self.dtype).itemsize
        layers = self.num_layers
        dense = min(self.first_dense_layers, layers)
        qk = self.num_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)
        shared = self.num_shared_experts * self.moe_intermediate_size
        parts = {
            moe.ROUTING: (layers - dense) * moe.routing_bytes(
                rows, self.router_experts, self.num_experts_per_tok),
            scopes.KEEP_OUT_PROJ: layers * rows * self.hidden_size * size,
            scopes.KEEP_Q_PROJ: layers * rows * qk * size,
            flash.FLASH_Q: layers * rows * qk * size,
            scopes.KEEP_MLP: rows * 2 * size * (
                dense * self.intermediate_size + (layers - dense) * shared),
            scopes.KEEP_KV_PROJ: layers * rows * size
            * (self.kv_lora_rank + self.qk_rope_head_dim),
            flash.FLASH_K: layers * rows * qk * size,
            flash.FLASH_V: layers * rows * size
            * self.num_heads * self.v_head_dim,
        }
        held = (layers * (rows * self.hidden_size * size
                            + flash.residual_bytes(b, self.num_heads, s,
                                                   self.v_head_dim, size))
                + rows * self.vocab_size * 4)
        return parts, held

    @nn.compact
    def __call__(self, ids):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     embedding_init=_normal(), name="embed_tokens",
                     **kw)(ids)
        layer_cls = DecoderLayer
        if self.remat:
            layer_cls = recomputed(
                DecoderLayer, self, *self.recompute_parts(*ids.shape))
        attention = dict(
            num_heads=self.num_heads,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, kv_lora_rank=self.kv_lora_rank,
            rope_theta=self.rope_theta, q_init_std=self.q_init_std)
        moe = dict(
            num_experts=self.num_experts,
            router_experts=self.router_experts,
            first_expert=self.first_expert, top_k=self.num_experts_per_tok,
            expert_dim=self.moe_intermediate_size,
            shared_dim=self.num_shared_experts * self.moe_intermediate_size,
            scale=self.routed_scaling_factor,
            selection_bias=self.selection_bias,
            group_rows=self.moe_group_rows,
            capacity_factor=self.moe_capacity_factor)
        for i in range(self.num_layers):
            x = layer_cls(
                attention=attention,
                moe=None if i < self.first_dense_layers else moe,
                dense_width=self.intermediate_size, eps=self.rms_norm_eps,
                name=f"layers_{i}", **kw)(x)
        return lm_head(self, x, self.rms_norm_eps, RMSNorm)


def kanana2_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: a dense layer and
    two expert layers, four of eight experts held, q.k 24 and v 16."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, num_layers=3,
            intermediate_size=96, num_heads=4, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
            rope_theta=1e4, num_experts=4, router_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32).items():
        kw.setdefault(key, value)
    return Kanana2(**kw)
