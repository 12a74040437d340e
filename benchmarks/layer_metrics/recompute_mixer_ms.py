"""Device milliseconds a step spends computing the layers' mixers a second
time: ops with the recompute's mark and one of ``hvd_gdn``, ``hvd_attn``,
``hvd_mla`` on their ``tf_op`` path (the projections, head norms, rotary,
the k / v repeats, the convolution, the layout swaps: what a
``checkpoint_name`` on a projection's output would take out of the second
run, at that output's bytes a layer).  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, parts.MIXERS, recomputed_only=True)
