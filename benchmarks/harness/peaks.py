"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

The benchmark's own copy: no environment variable changes it, and a
device that is not here is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops: float      # bf16 FLOP/s
    hbm_bytes: float  # HBM bytes/s
    hbm_capacity: int  # bytes


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s.  "TPU v5 lite" is what jax 0.9.0 / libtpu 0.0.34 calls
    # the chip (PERF.md, PR 21).
    "TPU v5 lite": Peak(197e12, 819e9, 16 * 2 ** 30),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak for device kind {device_kind!r}; known "
            f"kinds: {sorted(PEAKS)}.  Add it to benchmarks/harness/"
            "peaks.py with its source") from None
