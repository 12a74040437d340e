"""The three flash kernels apart: which ops of a trace are which kernel, and
the operations and bytes each kernel's outputs *require* from its inputs.

``harness.flops.flash_train_required`` charges a training step's attention
seven products, however many kernels share them out.  The program has three
kernels, named ``hvd_flash_fwd`` / ``hvd_flash_dq`` / ``hvd_flash_dkv`` on
the ``tf_op`` path of their custom calls, and each is charged here for what
it alone has to compute: forward QK^T and PV; dq the scores again (only
their row sums were kept), dP and dQ; dkv the scores again, dP, dV and dK.
Nine products: the scores and dP that both backward kernels compute stay
visible as the gap between ``flash_roofline`` and the three shares.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from . import flops, trace

#: kernel -> (products of 2·b·h·s²·d, [b,h,s,d] tensors read and written,
#: float32 [b,h,s] row statistics read and written)
KERNELS = {
    # reads q, k, v; writes o and the row statistic the backward keeps
    "fwd": (2, 4, 1),
    # reads q, k, v, do, lse, delta; writes dq
    "dq": (3, 5, 2),
    # reads q, k, v, do, lse, delta; writes dk, dv
    "dkv": (4, 6, 2),
}


def scope(kernel: str) -> str:
    """The kernel's ``pallas_call`` name and ``jax.named_scope`` in
    ``horovod_tpu/ops/flash_attention.py``."""
    return "hvd_flash_" + kernel


def is_kernel(kernel: str) -> Callable[[trace.Op], bool]:
    name = scope(kernel)
    return lambda op: trace.is_mosaic_kernel(op) and name in op.tf_op


def required(kernel: str, batch: int, heads: int, seq: int, head_dim: int,
             *, causal: bool, layers: int,
             bytes_per_element: int = 2) -> Tuple[float, float]:
    """(operations, bytes) one step's calls of ``kernel`` need over
    ``layers`` layers, counted as ``flops.flash_train_required`` counts."""
    products, tensors, stats = KERNELS[kernel]
    product = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        product /= 2.0
    tensor = batch * heads * seq * head_dim * bytes_per_element
    rows = batch * heads * seq * 4
    return (layers * products * product,
            layers * float(tensors * tensor + stats * rows))


def kernel_ms(run, kernel: str) -> Optional[float]:
    """Device milliseconds a step spends in ``kernel``; ``None`` where the
    trace has no Mosaic kernel under that name (a cell without attention,
    or a program that does not name its kernels)."""
    seconds = run.reduced.op_seconds(is_kernel(kernel))
    return run.per_step_ms(seconds) if seconds > 0 else None


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """Share of its roofline ``kernel`` reaches, in %."""
    seconds = run.reduced.op_seconds(is_kernel(kernel))
    if seconds <= 0:
        return None
    cfg, mix = run.cell.cfg, run.cell.mix
    need = required(
        kernel, int(mix["rows_per_chip"]), cfg["n_head"],
        int(mix["arrays"][0]["shape"][0]), cfg["n_embd"] // cfg["n_head"],
        causal=True, layers=cfg["n_layer"])
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"flash_{kernel}_roofline: {need[0]:.4g} operations and "
          f"{need[1]:.4g} bytes a step, {bound}-bound, least "
          f"{least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / (seconds / run.steps)
