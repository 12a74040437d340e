"""Share of their roofline the three flash kernels reach under the
sliding-window mask: the least time the chip's published peaks allow for the
seven products the window layers' attention needs over the *allowed* pairs,
exactly (``harness.mellum2_parts.flash_train_required``: ``window`` keys a
row, fewer for the first ``window`` rows; k and v count at the q heads'
number, as the kernels take them), over the time of the Mosaic kernels named
``hvd_flash_fwd`` / ``_dq`` / ``_dkv`` with ``hvd_attn_window`` on their
path.  A tile's masked pairs (a query block of 1024 rows visits 2048 keys
for 1024 allowed) and the grid steps that are visited to be skipped are time
and not required work, so they lower the share."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.flash_roofline(run, parts.SLIDING, "flash_swa_roofline")
