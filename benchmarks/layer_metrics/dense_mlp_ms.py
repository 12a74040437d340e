"""Device milliseconds a step spends in the dense SwiGLU feed-forward
parts: ops with ``hvd_dense_mlp`` on their ``tf_op`` path as a whole
component (gate, up and down projections and the gate's product), first
run, recompute and transposes.  Interval arithmetic.  Device trace."""

from benchmarks.harness import part_scopes


def read(run):
    return part_scopes.scope_ms(run, (part_scopes.DENSE_MLP,))
