"""Kanana-2's (``deepseek_v3``) own layers for the benchmark: its
parameters, the operations a token *requires*, what the flash kernels at a
q.k head size apart from v's and the expert layer under the sigmoid router
have to compute and move, and which ops of a device trace are latent
attention's.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed layer counts once).
"""

from __future__ import annotations

import math
from typing import Tuple

from . import flash_parts
from . import qwen3_next_parts as moe_parts

# the program's device scopes (docs/profiling.md)
MLA, MLA_LATENT = "hvd_mla", "hvd_mla_latent"
FLASH_KERNELS = ("fwd", "dq", "dkv")
#: kernel -> (products whose inner or outer width is q.k's, products at
#: v's, tensors of q.k's width read and written, tensors of v's width,
#: float32 row statistics).  fwd: QK^T | PV; q, k | v, o.  dq: the scores
#: again, dQ | dP; q, k, dq | v, do.  dkv: the scores again, dK | dP, dV; q,
#: k, dk | v, do, dv.
KERNELS = {"fwd": (1, 1, 2, 2, 1), "dq": (2, 1, 3, 2, 2),
           "dkv": (2, 2, 3, 3, 2)}


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's
    shapes."""
    from benchmarks.references import kanana2

    return sum(math.prod(s) for s in kanana2.param_shapes(cfg).values())


def head_dims(cfg: dict) -> Tuple[int, int]:
    """(q.k head size, v head size)."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def layer_counts(cfg: dict) -> Tuple[int, int]:
    """(leading dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def attention_matmul_params(cfg: dict) -> int:
    """``W_q``, ``W_kva``, ``W_kvb`` and ``W_o`` of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, dv = head_dims(cfg)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (d * h * qk + d * (rank + rope)
            + rank * h * (cfg["qk_nope_head_dim"] + dv) + h * dv * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_assignments_per_token(cfg: dict) -> float:
    """Of a token's ``num_experts_per_tok`` picks, how many an even router
    sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_num_experts"])


def causal_pairs(seq: int) -> int:
    """Pairs (query, key) a causal mask allows over ``seq`` rows."""
    return seq * (seq + 1) // 2


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token's forward pass: every layer's attention projections and
    the scores and values of its causal pairs (a head a pair: q.k over its
    head size, P v over v's); the dense layers' MLP; an expert layer's
    router, shared experts and the expected share of the held routed
    experts; the head.  The embedding is looked up."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, dv = head_dims(cfg)
    dense, sparse = layer_counts(cfg)
    attention = 2.0 * h * (qk + dv) * causal_pairs(seq) / seq
    expert_layer = (d * cfg["router_num_experts"]
                    + 3 * d * cfg["n_shared_experts"]
                    * cfg["moe_intermediate_size"]
                    + expected_assignments_per_token(cfg)
                    * expert_params(cfg))
    return (cfg["num_hidden_layers"]
            * (2.0 * attention_matmul_params(cfg) + attention)
            + dense * 2.0 * 3 * d * cfg["intermediate_size"]
            + sparse * 2.0 * expert_layer
            + 2.0 * d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _flash_units(cfg: dict, batch: int, seq: int, bytes_per_element: int):
    """(operations of one product a unit of head width, bytes of one
    ``[b, h, s, 1]`` column of elements, bytes of the row statistics)."""
    h = cfg["num_attention_heads"]
    return (2.0 * batch * h * causal_pairs(seq),
            batch * h * seq * bytes_per_element, batch * h * seq * 4)


def flash_kernel_required(cfg: dict, kernel: str, batch: int, seq: int,
                          bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one step's calls of one kernel (``fwd``, ``dq``,
    ``dkv``) need over all layers: that kernel's products, each at its own
    width (``KERNELS``), over the causal pairs; its tensors at the q heads'
    number, each at its own width; the float32 row statistics."""
    qk, dv = head_dims(cfg)
    wide, narrow, wide_t, narrow_t, stats = KERNELS[kernel]
    product, column, rows = _flash_units(cfg, batch, seq, bytes_per_element)
    layers = cfg["num_hidden_layers"]
    return (layers * product * (wide * qk + narrow * dv),
            layers * float(column * (wide_t * qk + narrow_t * dv)
                           + stats * rows))


def flash_train_required(cfg: dict, batch: int, seq: int,
                         bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's attention kernels need over
    all layers, counted as ``flops.flash_train_required`` counts a call of
    one head size: seven products (QK^T and PV forward; the scores again,
    dP, dV, dK, dQ backward), four of them at q.k's width and three at v's;
    forward reads q, k, v and writes o and the row statistics, backward
    reads q, k, v, o, do and the statistics and writes dq, dk, dv."""
    qk, dv = head_dims(cfg)
    product, column, rows = _flash_units(cfg, batch, seq, bytes_per_element)
    layers = cfg["num_hidden_layers"]
    return (layers * product * (4 * qk + 3 * dv),
            layers * float(column * (2 * qk + 2 * dv) + rows
                           + column * (4 * qk + 4 * dv) + 2 * rows))


def experts_train_required(cfg: dict, batch: int, seq: int,
                           bytes_per_element: int = 2
                           ) -> Tuple[float, float, float]:
    """(operations, bytes, assignments a layer) one training step's grouped
    expert products need over the expert layers, as
    ``qwen3_next_parts.experts_train_required`` counts them: three passes
    of three products over the assignments an even router sends to the held
    experts, each pass reading the held experts' weights once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * expected_assignments_per_token(cfg)
    weights = cfg["n_routed_experts"] * expert_params(cfg)
    ops = 3.0 * 2.0 * rows * expert_params(cfg)
    rows_bytes = rows * (2 * d + 3 * f) * bytes_per_element
    nbytes = 2 * (weights * bytes_per_element + rows_bytes) \
        + (weights * 4 + rows_bytes)
    layers = layer_counts(cfg)[1]
    return layers * ops, layers * float(nbytes), rows


# -- readers ---------------------------------------------------------------

def _shape(run) -> Tuple[int, int]:
    mix = run.cell.mix
    return int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0])


def scope_ms(run, scope: str):
    """Device milliseconds a step under ``scope`` (interval union);
    ``None`` where the trace has no such op."""
    return moe_parts.scope_ms(run, moe_parts.under(scope))


def flash_roofline(run):
    """The three kernels by name against the seven products; ``None`` where
    the configuration has no latent attention or the trace no such
    kernel."""
    if "kv_lora_rank" not in run.cell.cfg:
        return None
    kernels = [flash_parts.is_kernel(kernel) for kernel in FLASH_KERNELS]
    return moe_parts.roofline(
        run, "flash_mla_roofline", lambda op: any(k(op) for k in kernels),
        flash_train_required(run.cell.cfg, *_shape(run)))


def flash_kernel_roofline(run, kernel: str):
    """One kernel against its own 2 / 3 / 4 products."""
    if "kv_lora_rank" not in run.cell.cfg:
        return None
    return moe_parts.roofline(
        run, f"flash_mla_{kernel}_roofline", flash_parts.is_kernel(kernel),
        flash_kernel_required(run.cell.cfg, kernel, *_shape(run)))


def experts_roofline(run):
    """Ops under ``hvd_moe_experts`` against the expected assignments and
    the held experts' weights once a pass."""
    if "kv_lora_rank" not in run.cell.cfg:
        return None
    ops, nbytes, rows = experts_train_required(run.cell.cfg, *_shape(run))
    return moe_parts.roofline(
        run, "mla_experts_roofline", moe_parts.under(moe_parts.MOE_EXPERTS),
        (ops, nbytes), f" ({rows:.0f} expected assignments a layer)")
