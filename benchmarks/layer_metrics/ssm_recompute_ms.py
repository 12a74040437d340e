"""Device milliseconds a step spends computing the state-space mixers a
second time: ops with the recompute's mark and ``hvd_ssm`` on their
``tf_op`` path (``in_proj``, the convolution, the gated norm: what a
``checkpoint_name`` on each output would take out of the second run; the
scan keeps its output and carries no mark).  The accepted
``recompute_mixer_ms`` goes by ``hvd_gdn``, ``hvd_attn`` and ``hvd_mla`` and
reads this configuration's one attention block; this reads the rest.
Interval arithmetic.  Device trace."""

from benchmarks.harness import nemotron_h_parts as parts
from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (parts.SSM,), recomputed_only=True)
