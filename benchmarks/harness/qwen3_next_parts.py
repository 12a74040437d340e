"""Qwen3-Next's own layers for the benchmark: the operations a token
*requires*, what the chunked scan and the grouped expert products have to
compute and move, and which ops of a device trace belong to them.

Counted as ``harness/flops.py`` counts: a multiply-add is two operations,
from shapes alone, required work only (a recomputed layer counts once).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from . import flops, trace

# the program's device scopes (docs/profiling.md)
GDN, GDN_SCAN = "hvd_gdn", "hvd_gdn_scan"
MOE, MOE_ROUTE, MOE_EXPERTS = "hvd_moe", "hvd_moe_route", "hvd_moe_experts"


def _is_full_attention(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def layer_counts(cfg: dict) -> Tuple[int, int]:
    """(gated-DeltaNet layers, full-attention layers)."""
    full = sum(_is_full_attention(cfg, i)
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def expected_assignments_per_token(cfg: dict) -> float:
    """Of a token's ``num_experts_per_tok`` picks, how many an even router
    sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_num_experts"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token multiplies in one forward pass: the mixers'
    projections and the convolution's taps, the router, the shared expert
    and its gate, the expected share of the held routed experts, the head.
    The embedding is looked up, and norm weights are not matrix
    products."""
    d = cfg["hidden_size"]
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_dim = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    hv = cfg["linear_num_value_heads"]
    delta = (d * (2 * key_dim + 2 * value_dim) + d * 2 * hv
             + cfg["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
             + value_dim * d)
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention = d * h * hd * 2 + 2 * d * kv * hd + h * hd * d
    moe = (d * cfg["router_num_experts"]
           + 3 * d * cfg["shared_expert_intermediate_size"] + d
           + expected_assignments_per_token(cfg) * expert_params(cfg))
    n_delta, n_full = layer_counts(cfg)
    return (n_delta * delta + n_full * attention
            + cfg["num_hidden_layers"] * moe + d * cfg["vocab_size"])


def scan_products_per_token(cfg: dict) -> float:
    """Operations of the recurrence's three ``dk x dv`` products (``S^T
    k``, ``k u^T``, ``S^T q``) for every value head of one layer."""
    return 2.0 * 3 * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    n_delta, n_full = layer_counts(cfg)
    attention = 2.0 * seq * cfg["num_attention_heads"] * cfg["head_dim"]
    return (2.0 * matmul_params_per_token(cfg)
            + n_delta * scan_products_per_token(cfg) + n_full * attention)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward, and twice that for the backward pass."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def parameters(cfg: dict) -> int:
    """Every parameter the optimizer updates, from the reference's
    shapes."""
    import math

    from benchmarks.references import qwen3_next

    return sum(math.prod(s) for s in qwen3_next.param_shapes(cfg).values())


def scan_train_required(cfg: dict, batch: int, seq: int,
                        bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one training step's scans need over the
    gated-DeltaNet layers.  Operations: the recurrence's three products a
    head a token forward, and the backward pass counted the same way at
    twice that.  Bytes: q, k (per value head, as the recurrence takes
    them), v in and o out, g and beta in float32, in the forward pass; the
    same tensors and their gradients in the backward pass; and the float32
    states that must cross HBM — the one the program keeps for each chunk
    of 64 tokens, written once forward and read once backward, with its
    gradient written and read once beside it."""
    n_delta, _ = layer_counts(cfg)
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    tokens = batch * seq
    ops = 3.0 * tokens * scan_products_per_token(cfg)
    tensors = tokens * h * (2 * dk + 2 * dv) * bytes_per_element \
        + tokens * h * 2 * 4
    states = batch * h * (seq // 64) * dk * dv * 4
    return n_delta * ops, n_delta * float(3 * tensors + 4 * states)


def experts_train_required(cfg: dict, batch: int, seq: int,
                           bytes_per_element: int = 2
                           ) -> Tuple[float, float, float]:
    """(operations, bytes, assignments a layer) one training step's grouped
    expert products need over all layers, from the assignments an even
    router sends to the held experts.  Three passes (forward, gradient to
    the input, gradient to the weights) of three products each; every pass
    reads the held experts' weights once (the weight gradient writes them,
    in float32) and the assignments' rows in and out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * seq * expected_assignments_per_token(cfg)
    weights = cfg["num_experts"] * expert_params(cfg)
    ops = 3.0 * 2.0 * rows * expert_params(cfg)
    rows_bytes = rows * (2 * d + 3 * f) * bytes_per_element
    nbytes = 2 * (weights * bytes_per_element + rows_bytes) \
        + (weights * 4 + rows_bytes)
    layers = cfg["num_hidden_layers"]
    return layers * ops, layers * float(nbytes), rows


# -- which ops of a trace --------------------------------------------------

def under(scope: str) -> Callable[[trace.Op], bool]:
    return lambda op: scope in op.tf_op


def core_seconds(run, pred: Callable[[trace.Op], bool]) -> float:
    """Seconds in which an op ``pred`` picks was on the core, averaged over
    the chips.  Interval arithmetic, not a sum of durations: a scan is on
    the core's line as the envelope of its body's ops as well."""
    seconds = 0.0
    for chip in run.reduced.chips:
        seconds += trace.total(trace.union(
            (o.start, o.end) for o in chip.ops if pred(o)))
    return seconds / len(run.reduced.chips)


def scope_ms(run, pred: Callable[[trace.Op], bool]) -> Optional[float]:
    """``None`` where the trace has no such op (a program without the
    scope)."""
    seconds = core_seconds(run, pred)
    return run.per_step_ms(seconds) if seconds > 0 else None


def roofline(run, name: str, pred, need: Tuple[float, float],
             note: str = "") -> Optional[float]:
    seconds = core_seconds(run, pred)
    if seconds <= 0:
        return None
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"{name}: {need[0]:.4g} operations and {need[1]:.4g} bytes a "
          f"step{note}, {bound}-bound, least {least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / (seconds / run.steps)
