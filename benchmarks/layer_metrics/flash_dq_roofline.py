"""Share of its roofline the flash dq kernel (scores again, dP, dQ)
reaches: the least time the chip's published peaks allow for the products
its outputs need from its inputs (``harness.flash_parts.required``) over
``flash_dq_ms``."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_roofline(run, "dq")
