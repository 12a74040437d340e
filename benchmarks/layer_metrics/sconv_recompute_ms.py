"""Device milliseconds a step spends computing the gated short-convolution
operators a second time: ops with the recompute's mark and ``hvd_sconv`` on
their ``tf_op`` path (``in_proj``, the gates and taps, ``out_proj``: what a
``checkpoint_name`` on each output would take out of the second run), as
``ssm_recompute_ms`` reads ``hvd_ssm``.  The accepted ``recompute_mixer_ms``
goes by ``hvd_gdn``, ``hvd_attn`` and ``hvd_mla``; this reads the operator
it does not know.  Interval arithmetic.  Device trace."""

from benchmarks.harness import lfm2_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SCONV,), recomputed_only=True)
