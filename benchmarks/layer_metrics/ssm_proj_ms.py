"""Device milliseconds a step spends on the state-space mixers' two sides:
ops under ``hvd_ssm_in`` (``in_proj``, the split into z, xBC and dt,
``softplus(dt + dt_bias)``, what hands the scan its operands) or
``hvd_ssm_out`` (the gate with ``z``, the grouped norm, ``out_proj``):
first run, recompute and transposes.  With ``ssm_conv_ms`` and
``ssm_scan_ms`` it covers ``ssm_ms``.  Interval arithmetic.  Device
trace."""

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SSM_IN, parts.SSM_OUT))
