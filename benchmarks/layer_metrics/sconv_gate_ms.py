"""Device milliseconds a step spends between the gated short-convolution
operators' two products: ops under ``hvd_sconv_conv`` (``B * x``, the
causal depthwise taps, ``C * z``), first run, recompute and transposes.
Elementwise passes bound by memory.  Interval arithmetic.  Device trace."""

from benchmarks.harness import lfm2_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SCONV_CONV,))
