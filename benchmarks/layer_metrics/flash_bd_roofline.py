"""Share of their roofline the three flash kernels reach under the
block-diffusion mask: the least time the chip's published peaks allow for
the seven products one step's attention needs over the *allowed* pairs
(``harness.sdar_parts.flash_train_required``: ``L^2 + L B`` of the ``4
L^2`` a doubled sequence has; k and v count at the q heads' number, as the
kernels take them) over the time of the Mosaic kernels named
``hvd_flash_fwd`` / ``_dq`` / ``_dkv``.  A tile's masked pairs and a
recomputed forward kernel are time and not required work, so they lower
the share.  ``None`` where the configuration has no block length or the
trace no such kernel."""

from benchmarks.harness import flash_parts, flops
from benchmarks.harness import sdar_parts as parts


def read(run):
    cfg, mix = run.cell.cfg, run.cell.mix
    seconds = sum(run.reduced.op_seconds(flash_parts.is_kernel(kernel))
                  for kernel in parts.FLASH_KERNELS)
    if seconds <= 0 or "block_length" not in cfg:
        return None
    need = parts.flash_train_required(
        cfg, int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0]))
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"flash_bd_roofline: {need[0]:.4g} operations and {need[1]:.4g} "
          f"bytes a step, {bound}-bound, least {least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / (seconds / run.steps)
