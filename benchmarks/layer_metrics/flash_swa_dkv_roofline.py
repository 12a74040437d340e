"""Share of its roofline the flash dkv kernel (the scores again, dP, dV, dK) reaches
under the sliding-window mask: the least time the chip's published peaks
allow for that kernel's own products over the allowed pairs
(``harness.mellum2_parts.flash_kernel_required``) over the time of the
Mosaic kernel named ``hvd_flash_dkv`` with ``hvd_attn_window`` on its
path: ``flash_dkv_roofline``'s sibling for the window layers of a cell
whose layers differ by kind."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.flash_kernel_roofline(run, parts.SLIDING, "dkv",
                                       "flash_swa_dkv_roofline")
