"""Device milliseconds a step spends on the state-space mixers' causal
depthwise convolution over x, B and C together, its bias and SiLU: ops
under ``hvd_ssm_conv``, first run, recompute and transposes.  Interval
arithmetic.  Device trace."""

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SSM_CONV,))
