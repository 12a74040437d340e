"""Nemotron-H-style hybrid decoder (flax/linen), TPU-first: a stack of
blocks each of which is **one** mixer behind one norm and one residual add
— a Mamba-2 state-space layer (``M``), a mixture of non-gated relu^2
experts beside a shared one (``E``) or softmax attention (``*``) — in the
order a pattern string gives, with an untied head.

Block ``i`` of kind ``k = pattern[i]``: ``x <- x + mixer_k(RMSNorm(x))``;
after the last block a final RMSNorm and the head, logits in float32.
RMSNorm is ``x / rms(x) * w`` with a plain weight that starts at one.  No
bias but the convolution's, no dropout, no position table and no rotary
embedding (the attention layers see order through the state-space layers).

* ``M`` (:class:`Mamba2Mixer`): ``in_proj`` columns are ``[z | xBC | dt]``
  (``d_inner | d_inner + 2 groups state | heads``); a causal depthwise
  convolution of ``conv_kernel`` taps with a bias, then SiLU, over ``xBC``
  together; ``x`` to ``heads`` heads of ``head_dim``, ``B`` and ``C`` to
  ``groups`` groups of ``state``, a group serving ``heads / groups``
  consecutive heads; ``dt = softplus(dt + dt_bias)`` in float32 (no clamp),
  ``A = -exp(A_log)`` a head; the chunked scan of ``ops/ssd.py`` (``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``);
  ``y <- RMSNorm_grouped(y * silu(z))``: the gate first, then the norm over
  each of the ``groups`` groups of ``d_inner / groups`` columns, one weight
  a column; ``out_proj``.  Seeded as the source seeds it: ``A_log =
  log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a ``dt``
  drawn log-uniform on ``[dt_min, dt_max]`` and floored at ``dt_floor``.
* ``*`` (:class:`Attention`): grouped-query attention, q to ``num_heads``
  heads of ``head_dim``, k and v to ``num_kv_heads`` (the kernels take
  them at that count: a kv head serves its group of q heads by index
  map), causal softmax through the Pallas flash kernels at scale
  ``head_dim ** -0.5``, ``o_proj``.
* ``E`` (:class:`Relu2Moe`): ``parallel/moe.grouped_routed_experts`` over
  the experts held here (``num_experts`` of the router's
  ``router_experts``, from ``first_expert``) under
  ``moe.route_sigmoid_top_k`` (sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of ``score + bias``, the scores
  themselves as weights, normalised over the picks and multiplied by
  ``routed_scaling_factor``) with the experts' form ``"relu2"``:
  ``down_j(relu(up_j x) ** 2)``, two matrices and no gate; plus one shared
  expert of the same form every token takes.  ``selection_bias`` is a
  constant of the module (zeros unless given), never a parameter.  With
  ``moe_capacity_factor`` the load is bounded as GShard bounds it, a group
  of ``moe_group_rows`` rows at a time (``models/sdar.py`` has the same).

bf16 compute / float32 parameters like the other families.  ``remat``
recomputes each block in the backward pass
(``models/recompute.recomputed``): the unit of recompute, of the device
scopes and of the keep budget is a block of one part.  The flash forward
kernel's output and row statistics and the scan's output are always kept,
so a block calls either once a step; of the other outputs a second run
would make again, what fits the byte budget ``recompute`` reckons from the
device's memory and the shapes (:meth:`NemotronH.recompute_parts`), in rank
order: the router's logits, picks and order, the gated norm's output,
``q_proj``'s, q as the kernels take it, the shared expert's ``up``,
``in_proj``'s output, ``k_proj`` / ``v_proj``, the scan's operands, the
convolution's output, k and v as the kernels take them.  (A block's last
projection feeds nothing the block computes again, so its output has no
name here.)  Device scopes (``models/scopes.py``, docs/profiling.md):
``hvd_ssm`` (``hvd_ssm_in``, ``hvd_ssm_conv``, ``hvd_ssm_scan``,
``hvd_ssm_out``), ``hvd_attn`` (``hvd_attn_qkv``, the flash kernels' own,
``hvd_attn_out``), ``hvd_moe`` (``hvd_moe_route``, ``hvd_moe_experts``,
``hvd_moe_shared``), ``hvd_head``; counter
``hvd_ssm_layers_traced_total{heads,head_dim,state,groups,chunk}``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from .. import metrics
from ..ops import flash_attention as flash
from ..ops import ssd as ssd_ops
from ..ops.flash_attention import flash_attention
from ..ops.ssd import ssd
from ..parallel import moe
from ..parallel.moe import grouped_routed_experts, route_sigmoid_top_k
from . import scopes
from .qwen3_next import (_dense, _normal, causal_depthwise_conv, lm_head,
                         rms_normalise)
from .recompute import recomputed
from .sdar import RMSNorm

_F32 = jnp.float32
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: the published pattern of NVIDIA-Nemotron-3-Nano-30B-A3B: 23 ``M``, 23
#: ``E``, 6 ``*``
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform on
    ``[dt_min, dt_max]``, floored at ``dt_floor``: ``dt + log(-expm1(-dt))``
    is softplus's inverse."""
    def init(key, shape, dtype=_F32):
        dt = jnp.exp(jax.random.uniform(key, shape, _F32)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def a_log_init(key, shape, dtype=_F32):
    """``log(1), log(2), ..``: head ``i`` decays at rate ``i + 1``."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=_F32)).astype(dtype)


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    eps: float
    chunk: int = 128
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, u):
        b, s, d = u.shape
        h, p, g, n = self.num_heads, self.head_dim, self.groups, self.state
        inner, conv_dim = h * p, h * p + 2 * g * n
        metrics.record_ssm_layer(h, p, n, g, self.chunk)
        with jax.named_scope(scopes.SSM):
            with jax.named_scope(scopes.SSM_IN):
                zxbcdt = checkpoint_name(
                    _dense(inner + conv_dim + h, "in_proj", self)(u),
                    scopes.KEEP_SSM_IN_PROJ)
                z = zxbcdt[..., :inner]
                xbc = zxbcdt[..., inner:inner + conv_dim]
                dt_bias = self.param(
                    "dt_bias", dt_bias_init(self.dt_min, self.dt_max,
                                            self.dt_floor), (h,),
                    self.param_dtype)
                dt = jax.nn.softplus(
                    zxbcdt[..., inner + conv_dim:].astype(_F32)
                    + dt_bias.astype(_F32))
            with jax.named_scope(scopes.SSM_CONV):
                kernel = self.param("conv1d", _normal(),
                                    (self.conv_kernel, conv_dim),
                                    self.param_dtype)
                bias = self.param("conv_bias", nn.initializers.zeros,
                                  (conv_dim,), self.param_dtype)
                # SiLU's derivative reads the convolution's output, and its
                # own output is elementwise in it
                xbc = jax.nn.silu(checkpoint_name(
                    causal_depthwise_conv(xbc, kernel.astype(self.dtype))
                    + bias.astype(self.dtype), scopes.KEEP_SSM_CONV))
            # what hands the scan its operands is the input side's too
            with jax.named_scope(scopes.SSM_IN):
                x = xbc[..., :inner].reshape(b, s, h, p)
                B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
                C = xbc[..., inner + g * n:].reshape(b, s, g, n)
                a_log = self.param("A_log", a_log_init, (h,),
                                   self.param_dtype)
                skip = self.param("D", nn.initializers.ones, (h,),
                                  self.param_dtype)
                rate = -jnp.exp(a_log.astype(_F32))
            y = ssd(x, dt, rate, B, C, skip, chunk=self.chunk)
            with jax.named_scope(scopes.SSM_OUT):
                w = self.param("norm", nn.initializers.ones, (inner,),
                               self.param_dtype)
                # the gate first, then the norm a group of columns
                y = y.reshape(b, s, inner).astype(_F32) \
                    * jax.nn.silu(z.astype(_F32))
                y = rms_normalise(y.reshape(b, s, g, inner // g),
                                  self.eps).reshape(b, s, inner)
                y = checkpoint_name((y * w.astype(_F32)).astype(self.dtype),
                                    scopes.KEEP_SSM_NORM)
                return _dense(d, "out_proj", self)(y)


class Attention(nn.Module):
    """Grouped-query causal attention with no position signal of its
    own."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
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
            o = flash_attention(q, k, v, causal=True)
            with jax.named_scope(scopes.ATTN_OUT):
                return _dense(d, "o_proj", self)(o.reshape(b, s, h * hd))


def _relu2_mlp(module: nn.Module, x, width: int, prefix: str = ""):
    up = checkpoint_name(_dense(width, prefix + "up_proj", module)(x),
                         scopes.KEEP_MLP)
    return _dense(x.shape[-1], prefix + "down_proj", module)(
        jnp.square(jax.nn.relu(up)))


class Relu2Moe(nn.Module):
    """The experts held here of ``router_experts``, ``top_k`` a token by
    sigmoid scores and a selection bias, each ``down(relu(up x) ** 2)``,
    plus one shared expert of the same form and ``shared_dim`` that every
    token takes.  With a ``capacity_factor`` the rows are taken in groups
    of ``group_rows`` and an expert takes at most ``capacity_factor * group
    * top_k / router_experts`` rows of a group."""
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
            shapes = {"up_proj": (self.num_experts, d, self.expert_dim),
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
                capacity_factor=self.capacity_factor, form="relu2",
                route=functools.partial(route_sigmoid_top_k, bias=bias,
                                        scale=self.scale))
            with jax.named_scope(scopes.MOE_SHARED):
                shared = _relu2_mlp(self, x, self.shared_dim,
                                    "shared_experts_")
            return routed + shared


#: {a block's kind: its mixer}
MIXERS = {MAMBA: Mamba2Mixer, EXPERTS: Relu2Moe, ATTENTION: Attention}


class Block(nn.Module):
    """One norm, one mixer of ``kind``, one residual add."""
    kind: str
    mixer: dict
    eps: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    @nn.compact
    def __call__(self, x):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = RMSNorm(self.eps, name="norm", **kw)(x)
        return x + MIXERS[self.kind](name="mixer", **self.mixer, **kw)(h)


class NemotronH(nn.Module):
    """Token ids ``[b, s]`` -> logits ``[b, s, vocab_size]`` float32.

    The defaults are the published widths of
    NVIDIA-Nemotron-3-Nano-30B-A3B; the pattern (so the depth), the experts
    held here and the vocabulary are what a caller sizes."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 128            # held here
    router_experts: int = 128         # the router's width
    first_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    selection_bias: Optional[Sequence[float]] = None
    moe_group_rows: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    norm_eps: float = 1e-5
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = _F32

    def kinds(self) -> tuple:
        """Each block's kind."""
        kinds = tuple(self.pattern)
        if not kinds or set(kinds) - set(MIXERS):
            raise ValueError(f"a pattern is made of {sorted(MIXERS)}, "
                             f"not {self.pattern!r}")
        return kinds

    def mixers(self) -> dict:
        """``{kind: its mixer's arguments}``."""
        return {
            MAMBA: dict(
                num_heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                groups=self.mamba_groups, state=self.ssm_state_size,
                conv_kernel=self.conv_kernel, eps=self.norm_eps,
                chunk=self.chunk_size, dt_min=self.time_step_min,
                dt_max=self.time_step_max, dt_floor=self.time_step_floor),
            ATTENTION: dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim),
            EXPERTS: dict(
                num_experts=self.num_experts,
                router_experts=self.router_experts,
                first_expert=self.first_expert,
                top_k=self.num_experts_per_tok,
                expert_dim=self.moe_intermediate_size,
                shared_dim=self.shared_intermediate_size,
                scale=self.routed_scaling_factor,
                selection_bias=self.selection_bias,
                group_rows=self.moe_group_rows,
                capacity_factor=self.moe_capacity_factor)}

    def recompute_parts(self, b: int, s: int):
        """``(parts, held)`` for :func:`recompute.recomputed` over ``[b,
        s]`` ids: the bytes each name would keep over the blocks that have
        it, and the activations the step holds whatever is kept (the
        blocks' inputs, the flash kernels' and the scan's residuals, the
        logits)."""
        rows, size = b * s, jnp.dtype(self.dtype).itemsize
        kinds = self.kinds()
        n_m, n_e, n_a = (kinds.count(k) for k in (MAMBA, EXPERTS, ATTENTION))
        h, p = self.mamba_num_heads, self.mamba_head_dim
        g, n = self.mamba_groups, self.ssm_state_size
        inner, conv_dim = h * p, h * p + 2 * g * n
        q = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        parts = {
            moe.ROUTING: n_e * moe.routing_bytes(
                rows, self.router_experts, self.num_experts_per_tok),
            scopes.KEEP_SSM_NORM: n_m * rows * inner * size,
            scopes.KEEP_Q_PROJ: n_a * rows * q * size,
            flash.FLASH_Q: n_a * rows * q * size,
            scopes.KEEP_MLP: n_e * rows * self.shared_intermediate_size
            * size,
            scopes.KEEP_SSM_IN_PROJ: n_m * rows * (inner + conv_dim + h)
            * size,
            scopes.KEEP_KV_PROJ: n_a * rows * 2 * kv * size,
            ssd_ops.SSD_IN: n_m * ssd_ops.operand_bytes(b, s, h, p, g, n,
                                                        size),
            scopes.KEEP_SSM_CONV: n_m * rows * conv_dim * size,
            flash.FLASH_K: n_a * rows * kv * size,
            flash.FLASH_V: n_a * rows * kv * size,
        }
        held = (len(kinds) * rows * self.hidden_size * size
                + n_a * flash.residual_bytes(b, self.num_heads, s,
                                             self.head_dim, size)
                + n_m * ssd_ops.residual_bytes(b, s, h, p, size)
                + rows * self.vocab_size * 4)
        return parts, held

    @nn.compact
    def __call__(self, ids):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_size,
                     embedding_init=_normal(), name="embed_tokens",
                     **kw)(ids)
        block_cls = Block
        if self.remat:
            block_cls = recomputed(
                Block, self, *self.recompute_parts(*ids.shape))
        mixers = self.mixers()
        for i, kind in enumerate(self.kinds()):
            x = block_cls(kind=kind, mixer=mixers[kind], eps=self.norm_eps,
                          name=f"layers_{i}", **kw)(x)
        return lm_head(self, x, self.norm_eps, RMSNorm)


def nemotron_h_tiny(**kw):
    """A toy of the same shape for tests and CPU dry-runs: the published
    pattern's shorter repeating unit (``MEMEM*E``), four heads of 8 in two
    groups over a state of 16, four of eight experts held."""
    for key, value in dict(
            vocab_size=256, hidden_size=64, pattern=PATTERN[:7],
            mamba_num_heads=4, mamba_head_dim=8, mamba_groups=2,
            ssm_state_size=16, chunk_size=16, num_heads=4, num_kv_heads=2,
            head_dim=16, num_experts=4, router_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_intermediate_size=48).items():
        kw.setdefault(key, value)
    return NemotronH(**kw)
