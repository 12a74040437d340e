"""Qwen3-Next-style hybrid decoder (flax/linen), TPU-first: gated DeltaNet
linear attention in three layers of four, gated grouped-query softmax
attention in the fourth, and in every layer a top-k mixture of many small
experts beside one shared expert.

Layer ``i`` (0-based) is a full-attention layer if ``(i + 1) %
full_attention_interval == 0`` and a gated-DeltaNet layer otherwise.  Every
layer: ``x = x + mixer(norm(x))``, ``x = x + moe(norm(x))``; then a final
norm and an untied head, logits in float32.  No bias anywhere.  RMSNorm is
``x / rms(x) * (1 + w)`` (``w`` starts at zero), except the per-head norm
on the DeltaNet output, which has a plain weight and is gated:
``w * o / rms(o) * silu(z)``.

* Full attention: ``q_proj`` gives each head a query and an output gate
  (columns ``[head][query | gate]``); per-head RMSNorm on q and k; rotary
  embedding on the first ``partial_rotary_factor`` of each head's dims,
  halves paired (``rotate_half``); causal softmax attention through the
  Pallas flash kernels, which take k and v at their own head count (a kv
  head serves its group of q heads by the kernels' index maps);
  ``o_proj(attn * sigmoid(gate))``.
* Gated DeltaNet: ``in_proj_qkvz`` columns are ``[q | k | v | z]``, heads
  contiguous inside each, ``in_proj_ba`` columns ``[b | a]`` (this
  implementation's order); a causal depthwise convolution of
  ``conv_kernel`` taps and SiLU over ``[q | k | v]``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)`` in float32; q
  and k L2-normalised, q scaled by ``1/sqrt(dk)``; the chunked scan of
  ``ops/gated_delta.py``; the gated per-head norm; ``out_proj``.
* Expert layer: ``parallel/moe.routed_experts`` over the experts held here
  (``num_experts`` of the router's ``router_experts``, from
  ``first_expert``) plus ``sigmoid(x w_g) * shared_expert(x)``.

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each decoder layer in the backward pass
(``models/recompute.recomputed``): the layers' inputs are kept, always
what the flash and scan forward kernels wrote for their backward kernels
(a layer calls each forward kernel once a step) and, of the other outputs
a second run would make again, what fits the byte budget ``recompute``
reckons from the device's memory and the shapes the model is applied to
(:meth:`Qwen3Next.recompute_parts`), in rank order: the router's logits,
picks and order (``parallel/moe.ROUTING``), the gated norm's output, the
output projections', ``q_proj``'s, q as the flash kernels take it, the
shared expert's gate and up, ``in_proj_qkvz`` / ``_ba``, ``k_proj`` /
``v_proj``, the scan's operands, the convolution's output, k and v as the
flash kernels take them (names: ``models/scopes.py`` ``KEEP_*`` and the
kernels' own).  A part kept has no op with ``rematted_computation`` on its
path; counter ``hvd_recompute_kept_bytes_traced_total{name}`` says what
was kept and what was skipped.  Device scopes
(``models/scopes.py``, docs/profiling.md): ``hvd_attn`` (``hvd_attn_qkv``,
the flash kernels' own, ``hvd_attn_out``), ``hvd_gdn`` (``hvd_gdn_in``,
``hvd_gdn_conv``, ``hvd_gdn_scan``, ``hvd_gdn_out``), ``hvd_moe``
(``hvd_moe_route``, ``hvd_moe_experts``, ``hvd_moe_shared``), ``hvd_head``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..ops import flash_attention as flash
from ..ops import gated_delta as gdn
from ..ops.flash_attention import flash_attention
from ..ops.gated_delta import gated_delta_rule
from ..parallel import moe
from ..parallel.moe import routed_experts
from . import scopes
from .recompute import recomputed

_F32 = jnp.float32
#: what JAX (0.9.0) writes on the path of every op a ``jax.checkpoint``
#: computes a second time: ``.../checkpoint/rematted_computation/<layer>/
#: <scopes>/<primitive>``, the program's scopes after it.  The benchmark's
#: ``recompute_ms`` reads it; ``tests/test_part_scopes.py`` fails by name
#: when an upgrade renames it.
REMAT_MARK = "rematted_computation"


def flash_blocks(head_dim: int) -> dict:
    """Tile sizes for ``flash_attention`` at this head size.  The kernels'
    defaults were swept at head size 64 (PR 25).  dkv streams four query
    tiles a grid step, and at head size 256 with 1024-row tiles that is
    16 MiB of VMEM, the compiler's whole limit: the step compiled or not
    by where XLA put the kernel's outputs.  512-row tiles there."""
    return {"block_q": 512} if head_dim > 128 else {}


def _normal(std: float = 0.02):
    return nn.initializers.normal(stddev=std)


def rms_normalise(x, eps: float):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


class RMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)`` over the last dim, computed in float32."""
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        return (rms_normalise(x, self.eps)
                * (1.0 + w.astype(_F32))).astype(self.dtype)


def _dense(features: int, name: str, module: nn.Module):
    return nn.Dense(features, use_bias=False, dtype=module.dtype,
                    param_dtype=module.param_dtype, kernel_init=_normal(),
                    name=name)


def rotary_tables(positions, rotary_dim: int, theta: float):
    """``(cos, sin)``, each ``[s, rotary_dim]`` float32: the ``rotary_dim //
    2`` frequencies twice over, for halves paired as in ``rotate_half``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=_F32)
                                / rotary_dim))
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin):
    """``x``: ``[b, s, h, d]``; the first ``cos.shape[-1]`` dims of each
    head are rotated, the rest pass through."""
    r = cos.shape[-1]
    rot, rest = x[..., :r].astype(_F32), x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    rot = rot * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([rot.astype(x.dtype), rest], axis=-1)


class GatedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope(scopes.ATTN):
            with jax.named_scope(scopes.ATTN_QKV):
                qg = checkpoint_name(
                    _dense(h * hd * 2, "q_proj", self)(x),
                    scopes.KEEP_Q_PROJ).reshape(b, s, h, 2 * hd)
                q, gate = qg[..., :hd], qg[..., hd:]
                k, v = (checkpoint_name(
                    _dense(kv * hd, name, self)(x),
                    scopes.KEEP_KV_PROJ).reshape(b, s, kv, hd)
                    for name in ("k_proj", "v_proj"))
                norm = dict(eps=self.eps, dtype=self.dtype,
                            param_dtype=self.param_dtype)
                q = RMSNorm(name="q_norm", **norm)(q)
                k = RMSNorm(name="k_norm", **norm)(k)
                cos, sin = rotary_tables(jnp.arange(s), self.rotary_dim,
                                         self.rope_theta)
                q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            o = flash_attention(q, k, v, causal=True, **flash_blocks(hd))
            with jax.named_scope(scopes.ATTN_OUT):
                o = o * jax.nn.sigmoid(gate.astype(_F32)).astype(self.dtype)
                return checkpoint_name(
                    _dense(x.shape[-1], "o_proj", self)(
                        o.reshape(b, s, h * hd)), scopes.KEEP_OUT_PROJ)


def causal_depthwise_conv(x, kernel):
    """``y_t = sum_j kernel[j] * x_{t - (taps - 1) + j}`` per channel, zeros
    before the sequence's start.  ``x``: ``[b, s, c]``; ``kernel``:
    ``[taps, c]``."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * kernel[j] for j in range(taps))


class GatedDeltaNet(nn.Module):
    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int
    eps: float
    chunk: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        hk, hv, dk, dv = (self.num_k_heads, self.num_v_heads,
                          self.head_k_dim, self.head_v_dim)
        key_dim, value_dim = hk * dk, hv * dv
        with jax.named_scope(scopes.GDN):
            with jax.named_scope(scopes.GDN_IN):
                qkvz, ba = (checkpoint_name(
                    _dense(width, name, self)(x), scopes.KEEP_GDN_IN_PROJ)
                    for name, width in (
                        ("in_proj_qkvz", 2 * key_dim + 2 * value_dim),
                        ("in_proj_ba", 2 * hv)))
                qkv, z = qkvz[..., :2 * key_dim + value_dim], \
                    qkvz[..., 2 * key_dim + value_dim:]
            with jax.named_scope(scopes.GDN_CONV):
                kernel = self.param(
                    "conv1d", _normal(),
                    (self.conv_kernel, 2 * key_dim + value_dim),
                    self.param_dtype)
                # SiLU's derivative reads the convolution's output, and its
                # own output is elementwise in it
                qkv = jax.nn.silu(checkpoint_name(causal_depthwise_conv(
                    qkv, kernel.astype(self.dtype)), scopes.KEEP_GDN_CONV))
            # what prepares the scan's operands is the input side's too
            with jax.named_scope(scopes.GDN_IN):
                q = qkv[..., :key_dim].reshape(b, s, hk, dk)
                k = qkv[..., key_dim:2 * key_dim].reshape(b, s, hk, dk)
                v = qkv[..., 2 * key_dim:].reshape(b, s, hv, dv)
                a_log = self.param(
                    "A_log", lambda key, shape, dtype: jnp.log(
                        jax.random.uniform(key, shape, dtype, 1e-3, 16.0)),
                    (hv,), self.param_dtype)
                dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                                     self.param_dtype)
                beta = jax.nn.sigmoid(ba[..., :hv].astype(_F32))
                g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
                    ba[..., hv:].astype(_F32) + dt_bias.astype(_F32))

                def l2(t):
                    t = t.astype(_F32)
                    return t * jax.lax.rsqrt(
                        jnp.sum(jnp.square(t), axis=-1, keepdims=True)
                        + self.eps)

                q = (l2(q) * dk ** -0.5).astype(self.dtype)
                k = l2(k).astype(self.dtype)
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
            with jax.named_scope(scopes.GDN_OUT):
                w = self.param("norm", nn.initializers.ones, (dv,),
                               self.param_dtype)
                z = z.reshape(b, s, hv, dv).astype(_F32)
                o = checkpoint_name(
                    (w.astype(_F32) * rms_normalise(o, self.eps)
                     * jax.nn.silu(z)).astype(self.dtype),
                    scopes.KEEP_GDN_NORM)
                return checkpoint_name(
                    _dense(d, "out_proj", self)(o.reshape(b, s, value_dim)),
                    scopes.KEEP_OUT_PROJ)


class SparseMoe(nn.Module):
    """The experts held here of ``router_experts``, ``top_k`` a token, and
    one shared expert behind a sigmoid gate."""
    num_experts: int          # held here
    router_experts: int       # the router's width: all the layer's experts
    first_expert: int
    top_k: int
    expert_dim: int
    shared_dim: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        flat = x.reshape(b * s, d)
        with jax.named_scope(scopes.MOE):
            router = self.param("gate", _normal(), (d, self.router_experts),
                                self.param_dtype)
            shapes = {"gate_proj": (self.num_experts, d, self.expert_dim),
                      "up_proj": (self.num_experts, d, self.expert_dim),
                      "down_proj": (self.num_experts, self.expert_dim, d)}
            experts = {name: self.param(f"experts_{name}", _normal(), shape,
                                        self.param_dtype)
                       for name, shape in shapes.items()}
            routed = routed_experts(flat, router, experts, top_k=self.top_k,
                                    first_expert=self.first_expert)
            with jax.named_scope(scopes.MOE_SHARED):
                gate, up = (checkpoint_name(
                    _dense(self.shared_dim, name, self)(flat),
                    scopes.KEEP_MLP)
                    for name in ("shared_gate_proj", "shared_up_proj"))
                hidden = jax.nn.silu(gate) * up
                shared = _dense(d, "shared_down_proj", self)(hidden)
                gate = _dense(1, "shared_expert_gate", self)(flat)
                shared = shared * jax.nn.sigmoid(
                    gate.astype(_F32)).astype(self.dtype)
            return (routed + shared).reshape(b, s, d)


class DecoderLayer(nn.Module):
    full_attention: bool
    attention: dict
    linear_attention: dict
    moe: dict
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = RMSNorm(self.eps, name="input_layernorm", **kw)(x)
        if self.full_attention:
            h = GatedAttention(eps=self.eps, name="self_attn",
                               **self.attention, **kw)(h)
        else:
            h = GatedDeltaNet(eps=self.eps, name="linear_attn",
                              **self.linear_attention, **kw)(h)
        x = x + h
        h = RMSNorm(self.eps, name="post_attention_layernorm", **kw)(x)
        return x + SparseMoe(name="mlp", **self.moe, **kw)(h)


def lm_head(model: nn.Module, x, eps: float, norm_cls=RMSNorm):
    """The final norm and the untied head under ``hvd_head``: ``x`` ``[b, s,
    d]`` -> logits ``[b, s, vocab_size]`` float32.  For a compact ``model``
    with ``hidden_size``, ``vocab_size``, ``dtype`` and ``param_dtype``."""
    with jax.named_scope(scopes.HEAD):
        x = norm_cls(eps, name="norm", dtype=model.dtype,
                     param_dtype=model.param_dtype)(x)
        head = model.param("lm_head", _normal(),
                           (model.hidden_size, model.vocab_size),
                           model.param_dtype)
        return jnp.dot(x, head.astype(model.dtype),
                       preferred_element_type=_F32)


class Qwen3Next(nn.Module):
    """Token ids ``[b, s]`` -> logits ``[b, s, vocab_size]`` float32.

    The defaults are the published widths of Qwen3-Next-80B-A3B; depth,
    the experts held here and the vocabulary are what a caller sizes."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    num_experts: int = 512            # held here
    router_experts: int = 512         # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    scan_chunk: int = 64
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` ids: the bytes each name would keep over the layers that have
        it, and the activations the step holds whatever is kept (the
        layers' inputs, the kernels' residuals, the logits)."""
        rows, size = b * s, jnp.dtype(self.dtype).itemsize
        full = self.num_layers // self.full_attention_interval
        linear = self.num_layers - full
        d, q = self.hidden_size, self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        key = self.linear_num_key_heads * self.linear_key_head_dim
        hv = self.linear_num_value_heads
        value = hv * self.linear_value_head_dim
        parts = {
            moe.ROUTING: self.num_layers * moe.routing_bytes(
                rows, self.router_experts, self.num_experts_per_tok),
            scopes.KEEP_OUT_PROJ: self.num_layers * rows * d * size,
            scopes.KEEP_MLP: self.num_layers * rows * size
            * 2 * self.shared_expert_intermediate_size,
            scopes.KEEP_Q_PROJ: full * rows * 2 * q * size,
            scopes.KEEP_KV_PROJ: full * rows * 2 * kv * size,
            flash.FLASH_Q: full * rows * q * size,
            flash.FLASH_K: full * rows * kv * size,
            flash.FLASH_V: full * rows * kv * size,
            scopes.KEEP_GDN_IN_PROJ: linear * rows * size
            * (2 * key + 2 * value + 2 * hv),
            scopes.KEEP_GDN_CONV: linear * rows * (2 * key + value) * size,
            gdn.GDN_IN: linear * rows
            * ((2 * key + value) * size + 2 * hv * 4),
            scopes.KEEP_GDN_NORM: linear * rows * value * size,
        }
        held = (self.num_layers * rows * d * size
                + full * flash.residual_bytes(b, self.num_heads, s,
                                              self.head_dim, size)
                + linear * gdn.residual_bytes(
                    b, s, hv, self.linear_key_head_dim,
                    self.linear_value_head_dim, self.scan_chunk, size)
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
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            rotary_dim=int(self.head_dim * self.partial_rotary_factor))
        linear_attention = dict(
            num_k_heads=self.linear_num_key_heads,
            num_v_heads=self.linear_num_value_heads,
            head_k_dim=self.linear_key_head_dim,
            head_v_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel, chunk=self.scan_chunk)
        moe = dict(
            num_experts=self.num_experts,
            router_experts=self.router_experts,
            first_expert=self.first_expert, top_k=self.num_experts_per_tok,
            expert_dim=self.moe_intermediate_size,
            shared_dim=self.shared_expert_intermediate_size)
        for i in range(self.num_layers):
            x = layer_cls(
                full_attention=(i + 1) % self.full_attention_interval == 0,
                attention=attention, linear_attention=linear_attention,
                moe=moe, eps=self.rms_norm_eps, name=f"layers_{i}", **kw)(x)
        return lm_head(self, x, self.rms_norm_eps)


def qwen3_next_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: two periods of
    (DeltaNet, full attention), four of eight experts held."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, num_layers=4,
            full_attention_interval=2, num_heads=4, num_kv_heads=2,
            head_dim=16, partial_rotary_factor=0.5,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=4, router_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            scan_chunk=16).items():
        kw.setdefault(key, value)
    return Qwen3Next(**kw)
