"""Share of their roofline the three flash kernels reach at head size 64
under grouped-query attention, 32 q heads over 8 k/v heads repeated outside
the kernels: the least time the chip's published peaks allow for the seven
products one step's causal attention needs over the q heads
(``harness.lfm2_parts.flash_train_required``; k and v count at the q heads'
number, as the kernels take them) over the time of the Mosaic kernels named
``hvd_flash_fwd`` / ``_dq`` / ``_dkv``.  The scores and dP that both
backward kernels compute are time and not required work, so they lower the
share."""

from benchmarks.harness import lfm2_parts as parts


def read(run):
    return parts.flash_roofline(run)
