"""Share of their roofline the three flash kernels reach in the full
(causal) layers that stand beside sliding-window layers: the least time the
chip's published peaks allow for the seven products those layers' attention
needs over the causal pairs (``harness.mellum2_parts.flash_train_required``;
k and v count at the q heads' number, as the kernels take them) over the
time of the Mosaic kernels named ``hvd_flash_fwd`` / ``_dq`` / ``_dkv`` with
``hvd_attn_full`` on their path.  The scores and dP that both backward
kernels compute are time and not required work, so they lower the share."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.flash_roofline(run, parts.FULL, "flash_full_roofline")
