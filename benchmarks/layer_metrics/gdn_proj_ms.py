"""Device milliseconds a step spends on the gated-DeltaNet mixers' two
sides: ops under ``hvd_gdn_in`` (``in_proj_qkvz``, ``in_proj_ba``, the
split, and what prepares the scan's operands: the l2 norms, ``beta``, ``g``)
or ``hvd_gdn_out`` (the gated norm with ``z``, ``out_proj``): first run,
recompute and transposes.  With ``gdn_conv_ms`` and ``gdn_scan_ms`` it
covers ``gdn_ms``.  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.GDN_IN, parts.GDN_OUT))
