"""Device milliseconds a step spends computing the layers' feed-forward
blocks a second time: ops with the recompute's mark and ``hvd_moe`` or
``hvd_dense_mlp`` on their ``tf_op`` path (the router, top-k, the sort and
the tiles' gathers, the shared experts, the leading dense layer; the
experts' tile products are run again by ``parallel/moe``'s own backward rule
and carry no mark).  Interval arithmetic: a loop's envelope and its body
are one interval.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.MOE, parts.DENSE_MLP),
                          recomputed_only=True)
