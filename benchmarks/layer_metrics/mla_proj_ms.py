"""Device milliseconds a step spends on latent attention's plain
projections: ops under ``hvd_mla_q`` (``q_proj`` and the rotary embedding on
each head's last 64) or ``hvd_mla_out`` (``o_proj``): first run, recompute
and transposes.  With ``mla_latent_ms``, the three ``flash_*_ms`` and
``flash_layout_ms`` it covers ``mla_ms``.  Interval arithmetic.  Device
trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.MLA_Q, parts.MLA_OUT))
