"""Device milliseconds a step spends in what ``ops/flash_attention.
flash_attention`` does round its three kernels: ops under
``hvd_flash_layout`` (the swaps between the model's ``[b, s, h, d]`` and the
kernels' ``[b, h, s, d]`` and their transposes, the rows' log-sum-exp from
the forward kernel's statistics, the backward pass's ``delta``): first run,
recompute and transposes.  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.FLASH_LAYOUT,))
