"""Share of its roofline the flash dq kernel (the scores again, dP, dQ)
reaches under the block-diffusion mask: the least time the chip's published
peaks allow for that kernel's products over the *allowed* pairs
(``harness.sdar_parts.flash_kernel_required``) over the time of the Mosaic
kernel named ``hvd_flash_dq``: ``flash_dq_roofline``'s sibling for a cell
whose mask is not causal."""

from benchmarks.harness import sdar_parts as parts


def read(run):
    return parts.flash_kernel_roofline(run, "dq")
