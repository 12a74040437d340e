"""Device milliseconds a step spends on the gated short-convolution
operators' two products: ops under ``hvd_sconv_in`` (``in_proj`` and the
split into B, C and x) or ``hvd_sconv_out`` (``out_proj``): first run,
recompute and transposes.  With ``sconv_gate_ms`` it covers ``sconv_ms``.
Interval arithmetic.  Device trace."""

from benchmarks.harness import lfm2_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SCONV_IN, parts.SCONV_OUT))
