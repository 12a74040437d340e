"""Device milliseconds a step spends in the gated short-convolution
operators: ops with ``hvd_sconv`` on their ``tf_op`` path as a whole
component (``in_proj`` and the split, ``B * x``, the taps, ``C * z``,
``out_proj``), first run, recompute and transposes.  Interval arithmetic.
Device trace."""

from benchmarks.harness import lfm2_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SCONV,))
