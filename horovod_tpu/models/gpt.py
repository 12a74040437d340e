"""GPT-style decoder-only Transformer LM (flax/linen), TPU-first.

The long-context model family: causal attention defaults to the Pallas
flash kernels (ops/flash_attention.py) on TPU, and any attention override
— ring or Ulysses sequence parallelism with ``causal=True`` — plugs into
``attention_fn`` exactly as in the BERT encoder.  The reference ships no
model code (SURVEY §5); this family exists so the framework's benchmark
and long-context claims are self-contained.

TPU-first choices: bf16 compute / f32 params; pre-LN; attention and MLP
as einsums on the MXU; weight-tied LM head (one embedding matrix);
no Python control flow in the forward pass."""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .bert import EncoderLayer
from .scopes import HEAD


def causal_flash_attention_fn(q, k, v, mask):
    """Default causal core: flash kernels on TPU, interpreter off-TPU
    (ops/flash_attention.py resolves per mesh platform)."""
    from ..ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)


class GPT(nn.Module):
    """Decoder-only LM over token ids -> logits ``[b, s, vocab]``.

    ``attention_fn(q, k, v, mask)`` must apply causal masking itself
    (the default does; for sequence parallelism pass e.g.
    ``lambda q, k, v, m: ring_attention(q, k, v, causal=True,
    axis="sp")``)."""

    vocab_size: int = 50257
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_fn: Optional[Callable] = None
    # offset of this shard's first token in the global sequence — nonzero
    # under sequence parallelism, where position embeddings must be global
    def position_ids(self, ids, seq_offset):
        return seq_offset + jnp.arange(ids.shape[-1])[None, :]

    @nn.compact
    def __call__(self, ids, seq_offset: int = 0):
        attn = self.attention_fn or causal_flash_attention_fn
        embed = nn.Embed(self.vocab_size, self.hidden_dim,
                         param_dtype=self.param_dtype, dtype=self.dtype,
                         name="wte")
        x = embed(ids)
        x = x + nn.Embed(self.max_len, self.hidden_dim,
                         param_dtype=self.param_dtype, dtype=self.dtype,
                         name="wpe")(self.position_ids(ids, seq_offset))
        for _ in range(self.num_layers):
            x = EncoderLayer(
                self.num_heads, self.mlp_dim, dtype=self.dtype,
                param_dtype=self.param_dtype, attention_fn=attn,
            )(x)
        with jax.named_scope(HEAD):
            x = nn.LayerNorm(dtype=self.dtype,
                             param_dtype=self.param_dtype)(x)
            # weight-tied LM head: logits = x @ wte^T, f32 for the softmax
            logits = embed.attend(x.astype(self.param_dtype))
            return logits.astype(jnp.float32)


def gpt2_small(**kw):
    return GPT(**kw)


def gpt_tiny(**kw):
    """4-layer/128-dim variant for tests and CPU dry-runs."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_dim", 128)
    kw.setdefault("num_layers", 4)
    kw.setdefault("num_heads", 4)
    kw.setdefault("mlp_dim", 256)
    kw.setdefault("max_len", 512)
    return GPT(**kw)


def next_token_loss(logits, ids):
    """Shifted cross-entropy: predict ids[t+1] from position t.

    The mean over ``b * (s - 1)`` positions of ``logsumexp(logits[b, t]) -
    logits[b, t, ids[b, t + 1]]``, written so that neither pass holds a
    ``[b, s, V]`` array beside ``logits`` and its cotangent: every position
    is computed and the last of each sequence weighs nothing (no slice, so
    no pad on the way back), and the label's logit is picked by comparing a
    vocabulary iota with the target inside the row's reduction (no gather,
    so no scatter on the way back).
    """
    b, s, v = logits.shape
    tgt = jnp.roll(ids, -1, axis=1)
    hit = jnp.arange(v) == tgt[..., None]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.where(hit, logits, 0).sum(-1)
    weight = (jnp.arange(s) < s - 1) / (b * (s - 1))
    return jnp.sum((lse - picked) * weight)


def weighted_token_loss(logits, targets, weight):
    """``sum_i weight_i (logsumexp(logits_i) - logits_i[targets_i])`` over
    all positions, with no shift: what a masked-token loss is, the weights
    zero where nothing is predicted (``models/sdar.block_diffusion_loss``).
    :func:`next_token_loss`'s form: no log-probability tensor, and the
    target's logit is picked inside the row's reduction (no gather, so no
    scatter on the way back)."""
    hit = jnp.arange(logits.shape[-1]) == targets[..., None]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.where(hit, logits, 0).sum(-1)
    return jnp.sum((lse - picked) * weight)
