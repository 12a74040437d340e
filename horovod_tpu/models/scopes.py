"""The device scopes of the decoders and of the head: one list of names.

A scope is a ``jax.named_scope``: it changes an op's metadata (``op_name`` in
the compiled module, ``tf_op`` in a device trace) and nothing the compiler
lowers, so a program with and without them is one program.  The decoders
(``qwen3_next``, ``sdar``, ``kanana2``, ``mellum2``, ``nemotron_h``,
``lfm2``) put every matrix product, convolution, scan and kernel call of a
layer under exactly one *part*;
``gpt`` names its head.
A layer's two norms and its residual adds are elementwise and stay unnamed.
``docs/profiling.md`` has the table with each scope's reader, and
``tests/test_part_scopes.py`` holds the models to this list.

No name here contains another block's name (readers match substrings:
``hvd_gdn``, ``hvd_mla``, ``hvd_moe``, ``hvd_ssm``, ``hvd_sconv``,
``hvd_loss/``) unless it is nested in that block.
"""

from __future__ import annotations

from ..ops import flash_attention as _flash
from ..ops import gated_delta as _gdn
from ..ops import ssd as _ssd
from ..parallel import moe as _moe

# softmax attention (qwen3_next.GatedAttention, sdar.BlockDiffusionAttention,
# mellum2.Attention, nemotron_h.Attention, lfm2.Attention)
ATTN = "hvd_attn"
ATTN_QKV = "hvd_attn_qkv"    # q / k / v projections, head norms, rotary, repeat
ATTN_OUT = "hvd_attn_out"    # the output gate where there is one, o_proj
# where a decoder's attention layers differ by kind (mellum2): the kind,
# between ``hvd_attn`` and its parts, round the whole of a layer's attention
ATTN_WINDOW = "hvd_attn_window"  # a sliding-window layer
ATTN_FULL = "hvd_attn_full"      # a full (causal) layer beside window layers
# gated DeltaNet (qwen3_next.GatedDeltaNet)
GDN = "hvd_gdn"
GDN_IN = "hvd_gdn_in"        # in_proj_qkvz / _ba, the split, l2 norms, beta, g
GDN_CONV = "hvd_gdn_conv"    # the causal depthwise convolution and SiLU
GDN_OUT = "hvd_gdn_out"      # the gated norm with z, out_proj
# Mamba-2 state-space mixer (nemotron_h.Mamba2Mixer)
SSM = "hvd_ssm"
SSM_IN = "hvd_ssm_in"        # in_proj, the split, softplus(dt + dt_bias)
SSM_CONV = "hvd_ssm_conv"    # the causal depthwise convolution, bias and SiLU
SSM_OUT = "hvd_ssm_out"      # the gate with z, the grouped norm, out_proj
# the gated short convolution (lfm2.ShortConv)
SCONV = "hvd_sconv"
SCONV_IN = "hvd_sconv_in"    # in_proj and the split into B, C and x
SCONV_CONV = "hvd_sconv_conv"  # B * x, the causal depthwise taps, C * z
SCONV_OUT = "hvd_sconv_out"  # out_proj
# latent attention (kanana2.LatentAttention)
MLA = "hvd_mla"
MLA_Q = "hvd_mla_q"          # q_proj and its rotary part
MLA_LATENT = "hvd_mla_latent"  # kv_a_proj, its norm, kv_b_proj, assembling k
MLA_OUT = "hvd_mla_out"      # the layout transposes after the kernels, o_proj
# feed-forward blocks
DENSE_MLP = "hvd_dense_mlp"
MOE = "hvd_moe"
MOE_SHARED = "hvd_moe_shared"
# the final norm and the head's product (the loss is ``hvd_loss``, outside
# the model: ``training.py``)
HEAD = "hvd_head"
# block diffusion's input and the slice before the head (sdar.SDAR)
BD_NOISE = "hvd_bd_noise"
BD_HEAD_ROWS = "hvd_bd_head_rows"
# the rotary tables of each kind of layer, made once a step (mellum2.Mellum2)
ROTARY_TABLES = "hvd_rotary_tables"

# What a recomputed decoder layer may keep besides the Pallas kernels'
# residuals: ``jax.ad_checkpoint.checkpoint_name``s on the outputs the
# second run would make again, at the place that makes each (an identity
# outside a checkpoint).  ``models/recompute.py`` ranks them (with the
# names the kernels and ``parallel/moe`` give their own residuals) and
# keeps what fits its budget.
KEEP_OUT_PROJ = "hvd_keep_out_proj"    # out_proj / o_proj's output
KEEP_GDN_NORM = "hvd_keep_gdn_norm"    # the gated norm: out_proj's operand
KEEP_Q_PROJ = "hvd_keep_q_proj"        # q_proj's output (with its gate)
KEEP_KV_PROJ = "hvd_keep_kv_proj"      # k_proj, v_proj / kv_a_proj_with_mqa
KEEP_GDN_IN_PROJ = "hvd_keep_gdn_in_proj"  # in_proj_qkvz, in_proj_ba
KEEP_GDN_CONV = "hvd_keep_gdn_conv"    # the convolution's output, before SiLU
KEEP_MLP = "hvd_keep_mlp"              # a SwiGLU's gate and up outputs
KEEP_SSM_IN_PROJ = "hvd_keep_ssm_in_proj"  # in_proj: z, xBC and dt
KEEP_SSM_CONV = "hvd_keep_ssm_conv"    # the convolution's output, before SiLU
KEEP_SSM_NORM = "hvd_keep_ssm_norm"    # the gated norm: out_proj's operand
KEEP_SCONV_IN_PROJ = "hvd_keep_sconv_in_proj"  # in_proj: B, C and x
KEEP_SCONV_GATE = "hvd_keep_sconv_gate"  # C * z: out_proj's operand

#: ``ops/flash_attention.flash_attention``: its three kernels, and what it
#: does round them (the layout swaps, the rows' log-sum-exp, ``delta``)
FLASH_KERNELS = (_flash.FWD_KERNEL, _flash.DQ_KERNEL, _flash.DKV_KERNEL)
FLASH = (*FLASH_KERNELS, _flash.LAYOUT_SCOPE)

#: {a block of a decoder layer, or the head: its parts}.  Every product,
#: convolution and kernel call under the block is under exactly one of the
#: parts; a block with no part listed is its own.
PARTS = {
    ATTN: (ATTN_QKV, *FLASH, ATTN_OUT),
    GDN: (GDN_IN, GDN_CONV, _gdn.SCAN_SCOPE, GDN_OUT),
    SSM: (SSM_IN, SSM_CONV, _ssd.SCAN_SCOPE, SSM_OUT),
    SCONV: (SCONV_IN, SCONV_CONV, SCONV_OUT),
    MLA: (MLA_Q, MLA_LATENT, *FLASH, MLA_OUT),
    DENSE_MLP: (),
    MOE: (_moe.ROUTE_SCOPE, _moe.EXPERTS_SCOPE, MOE_SHARED),
    HEAD: (),
}
#: kernel names inside a part (each kernel's ``name=`` and scope)
NESTED = {_gdn.SCAN_SCOPE: (_gdn.FWD_KERNEL, _gdn.BWD_KERNEL)}
#: {a block: the kinds of its layers}, where a model's layers differ by
#: kind: one of them between the block and its parts on every path
KINDS = {ATTN: (ATTN_WINDOW, ATTN_FULL)}
#: what a model emits outside its layers and its head
OUTSIDE_LAYERS = (BD_NOISE, BD_HEAD_ROWS, ROTARY_TABLES)


def documented() -> tuple:
    """Every scope name of this list, blocks, parts and the kernels nested
    in them, once.  ``core`` folds it into the persistent compile cache's
    key: JAX keys a program without its metadata, and would hand a program
    that differs from a cached one in its names alone the cached
    executable, with the names of whoever compiled first."""
    names = list(OUTSIDE_LAYERS)
    for block, parts in PARTS.items():
        names += [block, *parts]
    for part, kernels in NESTED.items():
        names += [part, *kernels]
    for kinds in KINDS.values():
        names += kinds
    return tuple(dict.fromkeys(names))
