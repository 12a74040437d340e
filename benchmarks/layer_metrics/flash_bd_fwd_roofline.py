"""Share of its roofline the flash forward kernel (QK^T, online softmax, PV;
once a layer since PR 33: a recomputed layer keeps the kernel's output and
row statistics; it ran twice under ``nn.remat`` until then, required once,
and read half its share) reaches under the block-diffusion mask: the least time the chip's published
peaks allow for that kernel's products over the *allowed* pairs
(``harness.sdar_parts.flash_kernel_required``) over the time of the Mosaic
kernel named ``hvd_flash_fwd``: ``flash_fwd_roofline``'s sibling for a cell
whose mask is not causal."""

from benchmarks.harness import sdar_parts as parts


def read(run):
    return parts.flash_kernel_roofline(run, "fwd")
