"""Mellum-2's own layers for the benchmark: its parameters, the operations a
token *requires* where the layers' attention differs by kind (a sliding
window in most, all the keys in every fourth), what the flash kernels have to
compute and move over each kind's *allowed* pairs, and which ops of a device
trace are a window layer's or a full layer's.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed layer counts once).
"""

from __future__ import annotations

import math
from typing import Callable, List, Optional, Tuple

from . import flash_parts, part_scopes, trace
from . import qwen3_next_parts as moe_parts

SLIDING, FULL = "sliding_attention", "full_attention"
# the program's device scopes (docs/profiling.md): a layer's kind, inside
# ``hvd_attn`` and round the whole of the layer's attention
KIND_SCOPES = {SLIDING: "hvd_attn_window", FULL: "hvd_attn_full"}
FLASH_KERNELS = ("fwd", "dq", "dkv")


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's
    shapes."""
    from benchmarks.references import mellum2

    return sum(math.prod(s) for s in mellum2.param_shapes(cfg).values())


def layer_kinds(cfg: dict) -> List[str]:
    """The kinds of the layers held here: the first ``num_hidden_layers``
    entries of ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layers_of(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def allowed_pairs(cfg: dict, kind: str, seq: int) -> int:
    """Pairs (query, key) a layer of ``kind`` allows over ``seq`` rows: a
    full layer ``seq (seq + 1) / 2``; a window layer ``window`` a row, less
    what the first ``window`` rows have nothing behind them for."""
    if kind == FULL:
        return seq * (seq + 1) // 2
    window = min(cfg["sliding_window"], seq)
    return seq * window - window * (window - 1) // 2


def attention_matmul_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd


def layer_matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token multiplies in one layer: the attention
    projections, the router, and the expected share of the held experts
    (``num_experts_per_tok * held / all`` assignments from an even
    router)."""
    return (attention_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["router_num_experts"]
            + moe_parts.expected_assignments_per_token(cfg)
            * moe_parts.expert_params(cfg))


def attention_flops_per_token(cfg: dict, kind: str, seq: int) -> float:
    """One layer's scores and values a token, forward: two products of ``2
    head_dim`` operations a head over the kind's allowed pairs a row."""
    return (4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * allowed_pairs(cfg, kind, seq) / seq)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token's forward pass: every layer's products, each layer's
    attention over the pairs its kind allows, the head.  The embedding is
    looked up."""
    return (cfg["num_hidden_layers"] * 2.0
            * layer_matmul_params_per_token(cfg)
            + sum(attention_flops_per_token(cfg, kind, seq)
                  for kind in layer_kinds(cfg))
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _flash_units(cfg: dict, kind: str, batch: int, seq: int,
                 bytes_per_element: int):
    """(operations of one product over the kind's allowed pairs, bytes of
    one ``[b, h, s, d]`` tensor at the q heads' number, bytes of one
    float32 row statistic)."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return (2.0 * batch * h * hd * allowed_pairs(cfg, kind, seq),
            batch * h * seq * hd * bytes_per_element, batch * h * seq * 4)


def flash_train_required(cfg: dict, kind: str, batch: int, seq: int,
                         bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's attention kernels need over
    the layers of ``kind``, counted as ``flops.flash_train_required``
    counts a causal call: seven products (QK^T and PV forward; the scores
    again, dP, dV, dK, dQ backward) over the *allowed* pairs, exactly;
    forward reads q, k, v and writes o and the float32 row statistics,
    backward reads q, k, v, o, do and the statistics and writes dq, dk, dv
    (k and v at the q heads' number, as the kernels take them)."""
    product, tensor, rows = _flash_units(cfg, kind, batch, seq,
                                         bytes_per_element)
    layers = layers_of(cfg, kind)
    return (layers * 7.0 * product,
            layers * float((4 * tensor + rows) + (8 * tensor + 2 * rows)))


def flash_kernel_required(cfg: dict, kind: str, kernel: str, batch: int,
                          seq: int, bytes_per_element: int = 2
                          ) -> Tuple[float, float]:
    """(operations, bytes) one step's calls of one kernel (``fwd``, ``dq``,
    ``dkv``) need over the layers of ``kind``: the products and tensors
    ``flash_parts.KERNELS`` charges that kernel (2 / 3 / 4 products: nine
    together, the scores and dP are computed by both backward kernels), the
    products over the allowed pairs."""
    products, tensors, stats = flash_parts.KERNELS[kernel]
    product, tensor, rows = _flash_units(cfg, kind, batch, seq,
                                         bytes_per_element)
    layers = layers_of(cfg, kind)
    return (layers * products * product,
            layers * float(tensors * tensor + stats * rows))


# -- readers ---------------------------------------------------------------

def _shape(run) -> Tuple[int, int]:
    mix = run.cell.mix
    return int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0])


def _has_kinds(run) -> bool:
    return "layer_types" in run.cell.cfg and "sliding_window" in run.cell.cfg


def kind_ms(run, kind: str) -> Optional[float]:
    """Device milliseconds a step in the attention of the layers of
    ``kind``, whole: ops with the kind's scope on their path as a whole
    component (projections, kernels, layout, ``o_proj``; first run,
    recompute and transposes), interval union.  ``None`` where the trace
    has no such op."""
    return part_scopes.scope_ms(run, (KIND_SCOPES[kind],))


def _kernel_of(kind: str, kernel: str) -> Callable[[trace.Op], bool]:
    named, under = flash_parts.is_kernel(kernel), f"/{KIND_SCOPES[kind]}/"
    return lambda op: named(op) and under in op.tf_op


def flash_roofline(run, kind: str, name: str) -> Optional[float]:
    """The three kernels of the layers of ``kind`` (by name, and by the
    kind's scope on their path) against the seven products over the kind's
    allowed pairs; ``None`` where the configuration's layers have no kinds
    or the trace no such kernel."""
    if not _has_kinds(run):
        return None
    kernels = [_kernel_of(kind, kernel) for kernel in FLASH_KERNELS]
    return moe_parts.roofline(
        run, name, lambda op: any(k(op) for k in kernels),
        flash_train_required(run.cell.cfg, kind, *_shape(run)))


def flash_kernel_roofline(run, kind: str, kernel: str, name: str
                          ) -> Optional[float]:
    """One kernel of the layers of ``kind`` against its own 2 / 3 / 4
    products over the allowed pairs."""
    if not _has_kinds(run):
        return None
    return moe_parts.roofline(
        run, name, _kernel_of(kind, kernel),
        flash_kernel_required(run.cell.cfg, kind, kernel, *_shape(run)))


def experts_roofline(run, name: str) -> Optional[float]:
    """Ops under ``hvd_moe_experts`` against the expected assignments and
    the held experts' weights once a pass
    (``qwen3_next_parts.experts_train_required`` from this configuration's
    keys)."""
    if not _has_kinds(run):
        return None
    ops, nbytes, rows = moe_parts.experts_train_required(
        run.cell.cfg, *_shape(run))
    return moe_parts.roofline(
        run, name, moe_parts.under(moe_parts.MOE_EXPERTS), (ops, nbytes),
        f" ({rows:.0f} expected assignments a layer)")
