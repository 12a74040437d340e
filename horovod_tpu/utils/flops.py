"""Analytic FLOP accounting for MFU reporting.

The ResNet bench reports MFU from the usual 3×-forward analytic count
(bench.py); this gives the transformer benches the same legibility
(reference docs/benchmarks.rst:66-80 publishes per-model throughput —
MFU is the hardware-normalized form).  Formula is the standard decoder
accounting (PaLM appendix B): 6·N FLOPs per token of parameter math
(fwd + bwd) plus the attention score/value matmuls, 12·L·s·d per token
— halved for causal models whose flash kernels skip fully-future
blocks.

This module is also the single source of the hardware peak numbers
every MFU/roofline consumer divides by: bench.py, chip_smoke.py and the
comm report's flops/peak fallback (timeline/comm_report.py) all route
through :func:`peak_flops` / :func:`hbm_bytes_per_sec`.  The peaks are
keyed by the mesh devices' ``device_kind``: a device that is not in
:data:`DEVICE_PEAKS` has no peak (no MFU is reported) unless
``HVD_PEAK_FLOPS`` names one explicitly.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np


class DevicePeak(NamedTuple):
    flops: float              # bf16 FLOP/s, per chip
    hbm_bytes_per_sec: float  # HBM bandwidth, per chip
    hbm_bytes: int            # HBM capacity, per chip


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM, 16 GB of it.  The kind string is what jax 0.9.0 / libtpu 0.0.34
    # reports on the chip (chip_smoke.py output, PR 21).
    "TPU v5 lite": DevicePeak(197e12, 819e9, 16 * 2 ** 30),
}

#: Operations ResNet-50 v1.5 at 224x224 requires per image and training
#: step, a multiply-add counted as two: 4.09 G multiply-adds forward, the
#: gradient to the input and to the weights of every convolution and of the
#: classifier, less the stem's input gradient, which nothing upstream
#: wants.  Conv by conv in benchmarks/harness/flops.py
#: (``resnet50_train_flops_per_image``), which a tier-1 test holds this
#: constant to.  It read 12.27e9 until PR 24: multiply-adds counted as
#: operations, so every MFU built on it read half.
RESNET50_TRAIN_FLOPS_PER_IMG = 24.30e9


def _mesh_device_kind() -> Optional[str]:
    """``device_kind`` of the devices the framework runs on; None before
    ``hvd.init()`` (asking JAX here would start a backend as a side
    effect, and on a chip host take the chip)."""
    from .. import core

    if not core.is_initialized():
        return None
    return core.mesh().devices.flat[0].device_kind


def _table_peak(kind: Optional[str]) -> Optional[DevicePeak]:
    return DEVICE_PEAKS.get(kind if kind is not None
                            else _mesh_device_kind())


def peak_flops(kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak FLOP/s for MFU math: ``HVD_PEAK_FLOPS`` when set,
    else the :data:`DEVICE_PEAKS` entry for ``kind`` (default: the mesh
    devices' kind), else None — there is no default device."""
    from .env import HVD_PEAK_FLOPS, get_float

    override = get_float(HVD_PEAK_FLOPS, 0.0)
    if override > 0:
        return override
    peak = _table_peak(kind)
    return peak.flops if peak else None


def hbm_bytes_per_sec(kind: Optional[str] = None) -> Optional[float]:
    """Per-chip HBM bandwidth for roofline math (the ridge point is
    ``peak_flops / hbm_bytes_per_sec`` flops/byte): the
    :data:`DEVICE_PEAKS` entry for ``kind`` (default: the mesh devices'
    kind), else None."""
    peak = _table_peak(kind)
    return peak.hbm_bytes_per_sec if peak else None


def hbm_bytes(kind: Optional[str] = None) -> Optional[int]:
    """Per-chip HBM capacity from :data:`DEVICE_PEAKS` for ``kind`` (default:
    the mesh devices' kind), else None.  By kind and not from the
    allocator's ``memory_stats``: a program compiled for a described device,
    which has no allocator, must be the program the chip runs."""
    peak = _table_peak(kind)
    return peak.hbm_bytes if peak else None


def require_peak_flops() -> float:
    """:func:`peak_flops` for callers that publish an MFU (bench.py,
    chip_smoke.py): an unknown device is an error, not a default."""
    peak = peak_flops()
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s for device kind {_mesh_device_kind()!r}: "
            f"known kinds are {sorted(DEVICE_PEAKS)}; add the device to "
            "horovod_tpu/utils/flops.py DEVICE_PEAKS with its source, or "
            "set HVD_PEAK_FLOPS")
    return peak


def param_count(params) -> int:
    return int(sum(np.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(params)))


def image_model_mfu(img_per_sec_per_chip: float,
                    flops_per_image: float = RESNET50_TRAIN_FLOPS_PER_IMG,
                    *, peak: Optional[float] = None) -> Optional[float]:
    """MFU of an image model from measured per-chip throughput — the
    bench.py headline math, single-sourced so the bench JSON and the
    ``hvd_mfu`` gauge agree by construction.  None when the device has
    no known peak."""
    peak = peak if peak is not None else peak_flops()
    if peak is None:
        return None
    return float(img_per_sec_per_chip) * float(flops_per_image) / peak


def transformer_train_flops_per_seq(n_params: int, num_layers: int,
                                    hidden_dim: int, seq_len: int, *,
                                    causal: bool = False) -> float:
    attn_per_token = 12.0 * num_layers * seq_len * hidden_dim
    if causal:
        attn_per_token /= 2.0
    return seq_len * (6.0 * n_params + attn_per_token)


def transformer_mfu(seq_per_sec_per_chip: float, n_params: int,
                    num_layers: int, hidden_dim: int, seq_len: int, *,
                    causal: bool = False,
                    peak: Optional[float] = None) -> Optional[float]:
    """Analytic transformer MFU; None when the device has no known
    peak."""
    peak = peak if peak is not None else peak_flops()
    if peak is None:
        return None
    fps = transformer_train_flops_per_seq(
        n_params, num_layers, hidden_dim, seq_len, causal=causal,
    )
    return seq_per_sec_per_chip * fps / peak
