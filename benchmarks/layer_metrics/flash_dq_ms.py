"""Device milliseconds a step spends in the flash dq kernel (scores again, dP, dQ): Mosaic kernels
under the ``hvd_flash_dq`` scope.  With the other two it adds up to
``flash_ms``.  Device trace."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_ms(run, "dq")
