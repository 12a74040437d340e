"""Nemotron-H's own layers for the benchmark: its parameters, the operations
a token *requires*, what the state-space scan and the relu^2 expert products
have to compute and move, and which ops of a device trace are the
state-space mixer's.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed block counts once).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

from benchmarks.references.nemotron_h import (ATTENTION, EXPERTS, MAMBA,
                                              kinds, mamba_dims,
                                              param_shapes)

from . import flops, trace
from . import qwen3_next_parts as moe_parts
# the same keys as the other configuration with 8 of 128 experts held
from .kanana2_parts import causal_pairs, expected_assignments_per_token

# the program's device scopes (docs/profiling.md)
SSM, SSM_IN, SSM_CONV, SSM_SCAN, SSM_OUT = (
    "hvd_ssm", "hvd_ssm_in", "hvd_ssm_conv", "hvd_ssm_scan", "hvd_ssm_out")


def block_counts(cfg: dict) -> Tuple[int, int, int]:
    """(state-space blocks, expert blocks, attention blocks)."""
    pattern = kinds(cfg)
    return tuple(pattern.count(k) for k in (MAMBA, EXPERTS, ATTENTION))


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's
    shapes."""
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def mamba_matmul_params(cfg: dict) -> int:
    """``in_proj``, the convolution's taps and ``out_proj`` of one block."""
    inner, conv_dim = mamba_dims(cfg)
    d = cfg["hidden_size"]
    return (d * (inner + conv_dim + cfg["mamba_num_heads"])
            + cfg["conv_kernel"] * conv_dim + inner * d)


def scan_products_per_token(cfg: dict) -> float:
    """Operations of the recurrence's three ``P x N`` products a head (the
    decay of the state, ``dt x B^T`` added to it, ``h C``) for every head
    of one block."""
    return 2.0 * 3 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def attention_matmul_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` of one block."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token's forward pass: a state-space block's projections,
    convolution and recurrence; an attention block's projections and the
    scores and values of its causal pairs; an expert block's router, shared
    expert and the expected share of the held routed experts; the head.
    The embedding is looked up."""
    d = cfg["hidden_size"]
    n_m, n_e, n_a = block_counts(cfg)
    mamba = 2.0 * mamba_matmul_params(cfg) + scan_products_per_token(cfg)
    attention = 2.0 * attention_matmul_params(cfg) \
        + 2.0 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * causal_pairs(seq) / seq
    experts = 2.0 * (d * cfg["router_num_experts"]
                     + 2 * d * cfg["moe_shared_expert_intermediate_size"]
                     + expected_assignments_per_token(cfg)
                     * expert_params(cfg))
    return (n_m * mamba + n_a * attention + n_e * experts
            + 2.0 * d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def scan_train_required(cfg: dict, batch: int, seq: int,
                        bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's scans need over the
    state-space blocks, whatever implements them.  Operations: the
    recurrence's three products a head a token forward, and the backward
    pass counted the same way at twice that.  Bytes: x, B and C (once a
    group) in and y out, ``dt`` in float32, once each in the forward pass;
    the same tensors and their gradients, once each, in the backward pass.
    No state crosses HBM by requirement: a chunk's state is the
    implementation's."""
    n_m, _, _ = block_counts(cfg)
    h = cfg["mamba_num_heads"]
    inner, conv_dim = mamba_dims(cfg)
    tokens = batch * seq
    ops = 3.0 * tokens * scan_products_per_token(cfg)
    tensors = tokens * ((conv_dim + inner) * bytes_per_element + h * 4)
    return n_m * ops, n_m * float(3 * tensors)


def experts_train_required(cfg: dict, batch: int, seq: int,
                           bytes_per_element: int = 2
                           ) -> Tuple[float, float, float]:
    """(operations, bytes, assignments a block) one training step's grouped
    expert products need over the expert blocks, as
    ``qwen3_next_parts.experts_train_required`` counts them for three
    matrices: here three passes of *two* products over the assignments an
    even router sends to the held experts (a filled tile's rows), each pass
    reading the held experts' weights once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * expected_assignments_per_token(cfg)
    weights = cfg["n_routed_experts"] * expert_params(cfg)
    ops = 3.0 * 2.0 * rows * expert_params(cfg)
    rows_bytes = rows * (2 * d + 2 * f) * bytes_per_element
    nbytes = 2 * (weights * bytes_per_element + rows_bytes) \
        + (weights * 4 + rows_bytes)
    blocks = block_counts(cfg)[1]
    return blocks * ops, blocks * float(nbytes), rows


# -- readers ---------------------------------------------------------------

def _is_nemotron_h(run) -> bool:
    return "hybrid_override_pattern" in run.cell.cfg


def _shape(run) -> Tuple[int, int]:
    mix = run.cell.mix
    return int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0])


def scan_roofline(run) -> Optional[float]:
    """Ops under ``hvd_ssm_scan`` against the recurrence's required
    operations and the scan's tensors once each."""
    if not _is_nemotron_h(run):
        return None
    return moe_parts.roofline(
        run, "ssm_scan_roofline", moe_parts.under(f"/{SSM_SCAN}/"),
        scan_train_required(run.cell.cfg, *_shape(run)))


def experts_roofline(run) -> Optional[float]:
    """Ops under ``hvd_moe_experts`` against the expected assignments' two
    products and the held experts' weights once a pass."""
    if not _is_nemotron_h(run):
        return None
    ops, nbytes, rows = experts_train_required(run.cell.cfg, *_shape(run))
    return moe_parts.roofline(
        run, "relu2_experts_roofline",
        moe_parts.under(moe_parts.MOE_EXPERTS), (ops, nbytes),
        f" ({rows:.0f} expected assignments a block)")


def flash_roofline(run) -> Optional[float]:
    """The Mosaic kernels (the attention blocks' flash kernels: the scan is
    XLA) against the seven products one step's causal attention needs over
    the q heads (``flops.flash_train_required``; k and v count at the q
    heads' number, as the kernels take them).  A recomputed forward kernel
    is time and not required work."""
    if not _is_nemotron_h(run):
        return None
    cfg = run.cell.cfg
    batch, seq = _shape(run)
    return moe_parts.roofline(
        run, "flash_nope_roofline", trace.is_mosaic_kernel,
        flops.flash_train_required(
            batch, cfg["num_attention_heads"], seq, cfg["head_dim"],
            causal=True, layers=block_counts(cfg)[2]))
