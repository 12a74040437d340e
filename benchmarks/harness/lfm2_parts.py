"""LFM2's own layers for the benchmark: its parameters, the operations a
token *requires* where a layer's operator is a gated short convolution or
softmax attention and its feed-forward part dense or routed, what the gates
and taps of the convolution, the grouped expert products and the flash
kernels have to compute and move, and which ops of a device trace are the
convolution operator's.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed layer counts once).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

from benchmarks.references.lfm2 import (ATTENTION, CONV, head_dim, is_dense,
                                        kinds, param_shapes)

from . import flash_parts, flops
from . import qwen3_next_parts as moe_parts
from .kanana2_parts import causal_pairs
# the same keys as the other configurations that name their held experts
# ``num_experts``: the picks an even router sends here, one expert's three
# matrices
from .qwen3_next_parts import expected_assignments_per_token, expert_params

# the program's device scopes (docs/profiling.md)
SCONV, SCONV_IN, SCONV_CONV, SCONV_OUT = (
    "hvd_sconv", "hvd_sconv_in", "hvd_sconv_conv", "hvd_sconv_out")
FLASH_KERNELS = ("fwd", "dq", "dkv")


def layer_counts(cfg: dict) -> Tuple[int, int, int, int]:
    """(convolution layers, attention layers, dense layers, expert
    layers) of the layers held here."""
    held = kinds(cfg)
    dense = sum(is_dense(cfg, i) for i in range(len(held)))
    return (held.count(CONV), held.count(ATTENTION), dense,
            len(held) - dense)


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's shapes
    (the table counts once: it is the head)."""
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def sconv_matmul_params(cfg: dict) -> int:
    """``in_proj`` and ``out_proj`` of one convolution operator: the
    parameters a token multiplies in a matrix product."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def attention_matmul_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` of one attention operator."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token's forward pass: a convolution operator's two products; an
    attention operator's projections and the scores and values of its
    causal pairs; the dense SwiGLU; an expert part's router and the
    expected share of the held routed experts; the head.  The embedding is
    looked up.  What lies between the convolution operator's products is
    elementwise (two gates and ``L`` taps a channel: 16 384 operations a
    token of an operator's 33.6 M) and is left out, as every other
    elementwise pass is; ``sconv_gate_train_required`` counts it."""
    d = cfg["hidden_size"]
    n_conv, n_attn, n_dense, n_moe = layer_counts(cfg)
    attention = 2.0 * attention_matmul_params(cfg) \
        + 2.0 * 2 * cfg["num_attention_heads"] * head_dim(cfg) \
        * causal_pairs(seq) / seq
    experts = 2.0 * (d * cfg["router_num_experts"]
                     + expected_assignments_per_token(cfg)
                     * expert_params(cfg))
    return (n_conv * 2.0 * sconv_matmul_params(cfg) + n_attn * attention
            + n_dense * 2.0 * 3 * d * cfg["intermediate_size"]
            + n_moe * experts + 2.0 * d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def sconv_gate_train_required(cfg: dict, batch: int, seq: int,
                              bytes_per_element: int = 2
                              ) -> Tuple[float, float]:
    """(operations, bytes) one training step's gates and taps need over the
    convolution layers, between ``in_proj``'s output and ``out_proj``'s
    operand, whatever implements them.  Bytes: B, C and x in and ``y = C *
    z`` out forward (four ``[rows, d]`` tensors); dy, B, x and C in and dB,
    dC and dx out backward (seven), once each at the compute dtype; the
    taps and their gradient are ``L x d`` and not counted.  Operations:
    ``B * x``, ``L`` multiply-adds and ``C * z`` a channel forward, twice
    that backward.  Neither ``B * x`` nor ``z`` crosses HBM by requirement:
    a pass that writes either, or a second forward run, lowers the
    share."""
    n_conv = layer_counts(cfg)[0]
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    tensor = batch * seq * d
    ops = 3.0 * tensor * (2 + 2 * taps)
    return n_conv * ops, n_conv * float(11 * tensor * bytes_per_element)


def experts_train_required(cfg: dict, batch: int, seq: int,
                           bytes_per_element: int = 2
                           ) -> Tuple[float, float, float]:
    """(operations, bytes, assignments a layer) one training step's grouped
    expert products need, as ``qwen3_next_parts.experts_train_required``
    counts a layer (three passes of three products over the assignments an
    even router sends to the held experts, each pass reading the held
    experts' weights once), over the *expert* layers alone: that function
    charges every held layer, and the leading dense layer has no expert."""
    ops, nbytes, rows = moe_parts.experts_train_required(
        cfg, batch, seq, bytes_per_element)
    share = layer_counts(cfg)[3] / cfg["num_hidden_layers"]
    return share * ops, share * nbytes, rows


def flash_train_required(cfg: dict, batch: int, seq: int
                         ) -> Tuple[float, float]:
    """(operations, bytes) of the seven products one step's causal
    attention needs over the q heads in the attention layers
    (``flops.flash_train_required``; k and v count at the q heads' number,
    as the kernels take them)."""
    return flops.flash_train_required(
        batch, cfg["num_attention_heads"], seq, head_dim(cfg), causal=True,
        layers=layer_counts(cfg)[1])


# -- readers ---------------------------------------------------------------

def _is_lfm2(run) -> bool:
    return "conv_L_cache" in run.cell.cfg


def _shape(run) -> Tuple[int, int]:
    mix = run.cell.mix
    return int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0])


def sconv_gate_roofline(run) -> Optional[float]:
    """Ops under ``hvd_sconv_conv`` against the gates' and taps' tensors
    once each."""
    if not _is_lfm2(run):
        return None
    return moe_parts.roofline(
        run, "sconv_gate_roofline", moe_parts.under(f"/{SCONV_CONV}/"),
        sconv_gate_train_required(run.cell.cfg, *_shape(run)))


def experts_roofline(run) -> Optional[float]:
    """Ops under ``hvd_moe_experts`` against the expected assignments' three
    products and the held experts' weights once a pass."""
    if not _is_lfm2(run):
        return None
    ops, nbytes, rows = experts_train_required(run.cell.cfg, *_shape(run))
    return moe_parts.roofline(
        run, "sconv_experts_roofline",
        moe_parts.under(moe_parts.MOE_EXPERTS), (ops, nbytes),
        f" ({rows:.0f} expected assignments a layer)")


def flash_roofline(run) -> Optional[float]:
    """The three flash kernels, by name, against the seven causal products
    at this configuration's heads.  The scores and dP that both backward
    kernels compute are time and not required work."""
    if not _is_lfm2(run):
        return None
    kernels = [flash_parts.is_kernel(kernel) for kernel in FLASH_KERNELS]
    return moe_parts.roofline(
        run, "flash_h64_gqa_roofline",
        lambda op: any(k(op) for k in kernels),
        flash_train_required(run.cell.cfg, *_shape(run)))
