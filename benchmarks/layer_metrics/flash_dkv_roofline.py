"""Share of its roofline the flash dkv kernel (scores again, dP, dV, dK)
reaches: the least time the chip's published peaks allow for the products
its outputs need from its inputs (``harness.flash_parts.required``) over
``flash_dkv_ms``."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_roofline(run, "dkv")
