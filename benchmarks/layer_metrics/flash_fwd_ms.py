"""Device milliseconds a step spends in the flash forward kernel (QK^T, online softmax, PV): Mosaic kernels
under the ``hvd_flash_fwd`` scope.  With the other two it adds up to
``flash_ms``.  Device trace."""

from benchmarks.harness import flash_parts


def read(run):
    return flash_parts.kernel_ms(run, "fwd")
