"""Share of their roofline the three flash kernels reach in attention
blocks without rotary or a position table, 32 q heads of 128 over 2 k/v
heads repeated outside the kernels: the least time the chip's published
peaks allow for the seven products one step's causal attention needs over
the q heads (``harness.nemotron_h_parts.flash_roofline``) over ``flash_ms``,
the time of the step's Mosaic kernels.  The scores and dP that both backward
kernels compute are time and not required work, so they lower the share."""

from benchmarks.harness import nemotron_h_parts as parts


def read(run):
    return parts.flash_roofline(run)
