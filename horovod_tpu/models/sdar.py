"""SDAR-style block-diffusion decoder (flax/linen), TPU-first: a Qwen3-MoE
decoder — plain RMSNorm, grouped-query attention with per-head q/k norms
and full rotary embedding, a top-k mixture of many small experts with no
shared expert, an untied head — *trained as a block-diffusion model*.

A row of data is ``(ids [L], level [L / B], draw [L])``: the tokens, one
noise level a block of ``B`` tokens and one draw a token, all integers.
With ``t_b = level_b / 65536`` the mask probability of block ``b``, token
``i`` is noised iff ``draw_i < level_{i // B}`` and then reads ``[MASK]``.
One forward pass serves every block at once: the decoder runs over the
noised copy and the clean copy side by side, ``2 L`` rows ``[noised ;
clean]`` that both carry positions ``0 .. L - 1``, under the block-diffusion
attention mask (``ops/flash_attention.block_diffusion_mask``): a clean row
sees the clean blocks up to its own, a noised row the clean blocks before
its own and the noised tokens of its own block, and nothing sees another
block's noise.  The final norm and the head run on the noised half alone:
logits ``[b, L, vocab]`` float32, one a data token, each predicting its own
token (no shift).  :func:`block_diffusion_loss` is the masked-token loss,
each masked position weighted by ``1 / t`` of its block.

Every layer: ``x = x + attn(norm(x))``, ``x = x + moe(norm(x))``; RMSNorm is
``x / rms(x) * w`` (``w`` starts at one, the heads' q / k norms at
``qk_norm_init``).  No bias anywhere.

* Attention: ``q_proj`` / ``k_proj`` / ``v_proj`` to ``num_heads`` /
  ``num_kv_heads`` heads of ``head_dim``; RMSNorm over each head's dims on
  q and on k (one weight of ``head_dim`` each, shared by the heads); rotary
  embedding over all of a head's dims, halves paired (``rotate_half``),
  **angles from the rows' position ids**; the Pallas flash kernels under
  the mask above, which take k and v at their ``num_kv_heads`` heads (a kv
  head serves its ``num_heads // num_kv_heads`` consecutive q heads by the
  kernels' index maps, and dk and dv leave the backward kernel summed over
  the group); ``o_proj``.
* Expert layer: ``parallel/moe.routed_experts`` over the experts held here
  (``num_experts`` of the router's ``router_experts``, from
  ``first_expert``): softmax over all the router's outputs in float32, the
  ``num_experts_per_tok`` largest, their weights divided by their sum.
  With ``moe_capacity_factor`` the load is bounded as GShard bounds it: a
  layer's rows are taken in groups of ``moe_group_rows`` and an expert
  takes at most ``factor * group * top_k / router_experts`` of a group's
  rows, the first in row order; what overflows is dropped.

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each decoder layer in the backward pass
(``models/recompute.recomputed``): the layers' inputs are kept, always the
flash forward kernel's output and row statistics, which its backward
kernels read (a layer calls the forward kernel once a step), and of the
other outputs a second run would make again what fits the byte budget
``recompute`` reckons from the device's memory and the shapes
(:meth:`SDAR.recompute_parts`), in rank order: the router's logits, picks
and order, ``o_proj``'s output, ``q_proj``'s, q as the kernels take it,
``k_proj`` / ``v_proj``'s, k and v as the kernels take them (at their own
head count: at the benchmark's size all of it fits).  A part kept has no op with ``rematted_computation`` on its
path; counter ``hvd_recompute_kept_bytes_traced_total{name}``.  Device
scopes (``models/scopes.py``, docs/profiling.md): ``hvd_bd_noise`` (the
compare, the substitution, the concatenation, the position ids),
``hvd_attn`` (``hvd_attn_qkv``, ``hvd_flash_*``, ``hvd_attn_out``),
``hvd_moe`` (``hvd_moe_route``, ``hvd_moe_experts``), ``hvd_bd_head_rows``
(the slice before the head), ``hvd_head``; counter
``hvd_bd_layers_traced_total{block}``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from .. import metrics
from ..ops import flash_attention as flash
from ..ops.flash_attention import block_diffusion_mask, flash_attention
from ..parallel import moe
from ..parallel.moe import grouped_routed_experts
from . import scopes
from .gpt import weighted_token_loss
from .qwen3_next import (_dense, _normal, apply_rotary, lm_head,
                         rms_normalise, rotary_tables)
from .recompute import recomputed

_F32 = jnp.float32
#: a noise level is an integer in ``[0, LEVELS]``: mask probability
#: ``level / LEVELS``; a token's draw is an integer in ``[0, LEVELS)``
LEVELS = 65536


def noised_tokens(ids, level, draw):
    """``[b, L]`` booleans: token ``i`` of a row is noised iff its draw is
    under its block's level (blocks of ``L / level.shape[1]`` tokens)."""
    block = ids.shape[1] // level.shape[1]
    return draw < jnp.repeat(level, block, axis=1)


class RMSNorm(nn.Module):
    """``x / rms(x) * w`` over the last dim, computed in float32; ``w``
    starts at ``init``."""
    eps: float = 1e-6
    init: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.constant(self.init),
                       (x.shape[-1],), self.param_dtype)
        return (rms_normalise(x, self.eps) * w.astype(_F32)).astype(
            self.dtype)


class BlockDiffusionAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_length: int
    eps: float
    qk_norm_init: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x, cos, sin):
        b, rows, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        metrics.record_bd_layer(self.block_length)
        with jax.named_scope(scopes.ATTN):
            with jax.named_scope(scopes.ATTN_QKV):
                q = checkpoint_name(
                    _dense(h * hd, "q_proj", self)(x),
                    scopes.KEEP_Q_PROJ).reshape(b, rows, h, hd)
                k, v = (checkpoint_name(
                    _dense(kv * hd, name, self)(x),
                    scopes.KEEP_KV_PROJ).reshape(b, rows, kv, hd)
                    for name in ("k_proj", "v_proj"))
                # the projections' scale cancels in these norms: their
                # weights are the softmax's temperature (the scores'
                # deviation is w_q * w_k)
                norm = dict(eps=self.eps, init=self.qk_norm_init,
                            dtype=self.dtype, param_dtype=self.param_dtype)
                q = RMSNorm(name="q_norm", **norm)(q)
                k = RMSNorm(name="k_norm", **norm)(k)
                q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            o = flash_attention(q, k, v, mask=block_diffusion_mask(
                self.block_length, rows // 2))
            with jax.named_scope(scopes.ATTN_OUT):
                return checkpoint_name(
                    _dense(x.shape[-1], "o_proj", self)(
                        o.reshape(b, rows, h * hd)), scopes.KEEP_OUT_PROJ)


class RoutedMoe(nn.Module):
    """The experts held here of ``router_experts``, ``top_k`` a token; no
    shared expert.  With a ``capacity_factor`` the rows are taken in
    groups of ``group_rows`` (all of them in one when fewer, or ``None``)
    and an expert takes at most ``capacity_factor * group * top_k /
    router_experts`` rows of a group."""
    num_experts: int          # held here
    router_experts: int       # the router's width: all the layer's experts
    first_expert: int
    top_k: int
    expert_dim: int
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
            return grouped_routed_experts(
                x, router, experts, top_k=self.top_k,
                first_expert=self.first_expert, group_rows=self.group_rows,
                capacity_factor=self.capacity_factor)


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
        x = x + BlockDiffusionAttention(eps=self.eps, name="self_attn",
                                        **self.attention, **kw)(h, cos, sin)
        h = RMSNorm(self.eps, name="post_attention_layernorm", **kw)(x)
        return x + RoutedMoe(name="mlp", **self.moe, **kw)(h)


class SDAR(nn.Module):
    """``(ids [b, L], level [b, L / block_length], draw [b, L])`` ->
    logits ``[b, L, vocab_size]`` float32, of the noised copy's rows.

    The defaults are the published widths of SDAR-30B-A3B-Chat; depth, the
    experts held here and the vocabulary are what a caller sizes.  The
    last id of the vocabulary is ``[MASK]`` unless ``mask_token_id`` names
    another."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128            # held here
    router_experts: int = 128         # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    moe_group_rows: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    qk_norm_init: float = 1.0
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    mask_token_id: int = -1
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def __call__(self, batch):
        ids, level, draw = batch
        length = ids.shape[1]
        if length != level.shape[1] * self.block_length:
            raise ValueError(
                f"{level.shape[1]} noise levels a row do not cover "
                f"{length} tokens in blocks of {self.block_length}")
        with jax.named_scope(scopes.BD_NOISE):
            mask_id = self.mask_token_id % self.vocab_size
            noised = jnp.where(noised_tokens(ids, level, draw), mask_id, ids)
            rows = jnp.concatenate([noised, ids], axis=1)
        return self.decode(rows)

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` rows (both copies): the bytes each name would keep over the
        layers, and the activations the step holds whatever is kept (the
        layers' inputs, the flash kernels' residuals, the noised half's
        logits)."""
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
                + rows // 2 * self.vocab_size * 4)
        return parts, held

    @nn.compact
    def decode(self, rows):
        """``rows`` ``[b, 2 L]``: the noised copy's ids, then the clean
        copy's -> logits ``[b, L, vocab_size]`` of the noised copy's rows.
        What ``__call__`` runs once it has noised its batch; on its own it
        takes the two copies as a caller made them."""
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        length = rows.shape[1] // 2
        with jax.named_scope(scopes.BD_NOISE):
            # both copies carry the data's positions
            positions = jnp.tile(jnp.arange(length), 2)
            cos, sin = rotary_tables(positions, self.head_dim,
                                     self.rope_theta)
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     embedding_init=_normal(), name="embed_tokens",
                     **kw)(rows)
        layer_cls = DecoderLayer
        if self.remat:
            layer_cls = recomputed(
                DecoderLayer, self, *self.recompute_parts(*rows.shape))
        attention = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, block_length=self.block_length,
            qk_norm_init=self.qk_norm_init)
        moe = dict(
            num_experts=self.num_experts,
            router_experts=self.router_experts,
            first_expert=self.first_expert, top_k=self.num_experts_per_tok,
            expert_dim=self.moe_intermediate_size,
            group_rows=self.moe_group_rows,
            capacity_factor=self.moe_capacity_factor)
        for i in range(self.num_layers):
            x = layer_cls(attention=attention, moe=moe,
                          eps=self.rms_norm_eps, name=f"layers_{i}",
                          **kw)(x, cos, sin)
        with jax.named_scope(scopes.BD_HEAD_ROWS):
            # the clean copy's rows predict nothing
            x = x[:, :length]
        return lm_head(self, x, self.rms_norm_eps, RMSNorm)


def block_diffusion_loss(logits, batch):
    """The masked-token loss of block diffusion under the linear schedule:
    ``(1 / (b L)) sum_i m_i (1 / t_{i // B}) (logsumexp(logits_i) -
    logits_i[ids_i])`` with ``m`` the noised tokens and ``t = level /
    LEVELS`` a block's mask probability.  No shift: a masked position
    predicts its own token.  ``batch`` is the model's input, ``(ids, level,
    draw)``."""
    ids, level, draw = batch
    b, length = ids.shape
    block = length // level.shape[1]
    weight = noised_tokens(ids, level, draw) * jnp.repeat(
        LEVELS / level.astype(_F32), block, axis=1) / (b * length)
    return weighted_token_loss(logits, ids, weight)


def sdar_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: two layers, four
    of eight experts held."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, rope_theta=1e4, num_experts=4,
            router_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, block_length=4).items():
        kw.setdefault(key, value)
    return SDAR(**kw)
