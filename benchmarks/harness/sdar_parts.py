"""SDAR's block-diffusion step for the benchmark: its parameters, the
operations a *data* token requires, and what the flash kernels under the
block-diffusion mask and the expert layer over the doubled sequence have to
compute and move.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed layer counts once).  A
data token is one of the ``L`` ids of a row; the decoder runs two rows for
it (its noised and its clean copy), the head one.
"""

from __future__ import annotations

import math
from typing import Tuple

from . import qwen3_next_parts as moe_parts

# the program's device scopes (docs/profiling.md)
BD_NOISE = "hvd_bd_noise"
FLASH_KERNELS = ("fwd", "dq", "dkv")


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's
    shapes."""
    from benchmarks.references import sdar

    return sum(math.prod(s) for s in sdar.param_shapes(cfg).values())


def allowed_pairs(seq: int, block: int) -> int:
    """Pairs (query row, key) the block-diffusion mask allows over the
    ``2 seq`` rows of one sequence: the block-diagonal quadrant ``seq
    block``, the strictly block-lower one ``(seq^2 - seq block) / 2``, the
    block-causal one ``(seq^2 + seq block) / 2``, the fourth none."""
    return seq * seq + seq * block


def layer_matmul_params_per_row(cfg: dict) -> float:
    """Parameters one decoder row multiplies in one layer: the attention
    projections, the router, and the expected share of the held experts
    (``num_experts_per_tok * held / all`` assignments a row from an even
    router).  Norm weights are not matrix products."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention = 2 * d * h * hd + 2 * d * kv * hd
    return (attention + d * cfg["router_num_experts"]
            + moe_parts.expected_assignments_per_token(cfg)
            * moe_parts.expert_params(cfg))


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One data token's forward pass: two decoder rows through every
    layer's products, the scores and values of its allowed pairs (two
    products of ``2 head_dim`` a head a pair; ``seq + block`` pairs a data
    token), the head on the noised row alone.  The embedding is looked
    up."""
    layers = cfg["num_hidden_layers"]
    pairs = allowed_pairs(seq, cfg["block_length"]) / seq
    attention = 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * pairs
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return (layers * (2 * 2.0 * layer_matmul_params_per_row(cfg) + attention)
            + head)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def flash_train_required(cfg: dict, batch: int, seq: int,
                         bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's attention kernels need over
    all layers under the block-diffusion mask, counted as
    ``flops.flash_train_required`` counts a causal call: seven products
    (QK^T and PV forward; the scores again, dP, dV, dK, dQ backward) of ``2
    head_dim`` operations a head over the *allowed* pairs; forward reads q,
    k, v and writes o and the float32 row statistics, backward reads q, k,
    v, o, do and the statistics and writes dq, dk, dv, all of ``2 seq``
    rows at the q heads' number (as the kernels take them)."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    product = 2.0 * batch * h * hd * allowed_pairs(seq, cfg["block_length"])
    tensor = batch * h * 2 * seq * hd * bytes_per_element
    rows = batch * h * 2 * seq * 4
    layers = cfg["num_hidden_layers"]
    return (layers * 7.0 * product,
            layers * float((4 * tensor + rows) + (8 * tensor + 2 * rows)))


def flash_kernel_required(cfg: dict, kernel: str, batch: int, seq: int,
                          bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one step's calls of one kernel (``fwd``, ``dq``,
    ``dkv``) need over all layers under the block-diffusion mask: the
    products and tensors ``flash_parts.KERNELS`` charges that kernel (2 / 3
    / 4 products: nine together, the scores and dP are computed by both
    backward kernels), the products over the *allowed* pairs."""
    from . import flash_parts

    products, tensors, stats = flash_parts.KERNELS[kernel]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    product = 2.0 * batch * h * hd * allowed_pairs(seq, cfg["block_length"])
    tensor = batch * h * 2 * seq * hd * bytes_per_element
    rows = batch * h * 2 * seq * 4
    layers = cfg["num_hidden_layers"]
    return (layers * products * product,
            layers * float(tensors * tensor + stats * rows))


def flash_kernel_roofline(run, kernel: str):
    """Share of its roofline one flash kernel reaches under the
    block-diffusion mask, in %: ``flash_parts.kernel_roofline`` with this
    configuration's heads and the allowed pairs.  ``None`` where the
    configuration has no block length or the trace no such kernel."""
    from . import flash_parts, flops

    cfg, mix = run.cell.cfg, run.cell.mix
    seconds = run.reduced.op_seconds(flash_parts.is_kernel(kernel))
    if seconds <= 0 or "block_length" not in cfg:
        return None
    need = flash_kernel_required(
        cfg, kernel, int(mix["rows_per_chip"]),
        int(mix["arrays"][0]["shape"][0]))
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"flash_bd_{kernel}_roofline: {need[0]:.4g} operations and "
          f"{need[1]:.4g} bytes a step, {bound}-bound, least "
          f"{least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / (seconds / run.steps)


def experts_train_required(cfg: dict, batch: int, seq: int
                           ) -> Tuple[float, float, float]:
    """(operations, bytes, assignments a layer) of the grouped expert
    products over the ``2 seq`` decoder rows of each sequence
    (``qwen3_next_parts.experts_train_required`` at that many rows)."""
    return moe_parts.experts_train_required(cfg, batch, 2 * seq)
