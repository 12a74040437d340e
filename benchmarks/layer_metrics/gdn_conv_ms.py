"""Device milliseconds a step spends in the gated-DeltaNet mixers' causal
depthwise convolution and its SiLU: ops under ``hvd_gdn_conv``, first run,
recompute and transposes.  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.GDN_CONV,))
