"""Device milliseconds a step spends in the chunked state-space scan
(``ops/ssd.py``): ops under ``hvd_ssm_scan``, the forward call and the
backward rule, which runs the chunk algebra again from the operands and
transposes it.  Interval arithmetic: the loop over chunks is on the core's
line with its body.  Device trace."""

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SSM_SCAN,))
