"""Share of their roofline the three flash kernels reach where q.k and v
have head sizes of their own (latent attention: 192 and 128): the least
time the chip's published peaks allow for the seven products one step's
causal attention needs, four at q.k's width and three at v's
(``harness.kanana2_parts.flash_train_required``; k and v count at the q
heads' number, as the kernels take them), over the time of the Mosaic
kernels named ``hvd_flash_fwd`` / ``_dq`` / ``_dkv``.  The scores and dP
that both backward kernels compute are time and not required work, so they
lower the share."""

from benchmarks.harness import kanana2_parts as parts


def read(run):
    return parts.flash_roofline(run)
