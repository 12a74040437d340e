"""Device milliseconds a step spends in the Mamba-2 state-space mixers: ops
with ``hvd_ssm`` on their ``tf_op`` path as a whole component (``in_proj``,
the convolution, the chunked scan forward and in its backward rule, the
gated grouped norm, ``out_proj``), first run, recompute and transposes.
Interval arithmetic: the scan's loop over chunks is on the core's line with
its body.  Device trace."""

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SSM,))
