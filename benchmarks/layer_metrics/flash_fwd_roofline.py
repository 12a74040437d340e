"""Share of its roofline the flash forward kernel (QK^T, online softmax, PV)
reaches: the least time the chip's published peaks allow for the products
its outputs need from its inputs (``harness.flash_parts.required``) over
``flash_fwd_ms``."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_roofline(run, "fwd")
