"""Device milliseconds a step spends in the flash dkv kernel (scores again, dP, dV, dK): Mosaic kernels
under the ``hvd_flash_dkv`` scope.  With the other two it adds up to
``flash_ms``.  Device trace."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_ms(run, "dkv")
