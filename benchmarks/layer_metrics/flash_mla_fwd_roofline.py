"""Share of its roofline the flash fwd kernel (QK^T at q.k's width, the online softmax, PV at v's)
reaches at latent attention's head sizes: the least time the chip's
published peaks allow for that kernel's own products over the causal pairs
(``harness.kanana2_parts.flash_kernel_required``) over the time of the
Mosaic kernel named ``hvd_flash_fwd``: ``flash_fwd_roofline``'s sibling
for a cell whose q.k and v head sizes differ."""

from benchmarks.harness import kanana2_parts as parts


def read(run):
    return parts.flash_kernel_roofline(run, "fwd")
