"""Device milliseconds a step spends computing decoder layers a second time:
every op with JAX's own mark of a ``jax.checkpoint``'s second run on its
``tf_op`` path (``checkpoint/rematted_computation/``: the projections, norms,
rotary, repeats, the convolution, the routing and the shared experts that
``models/qwen3_next.recomputed`` runs again from a layer's input; not the
Pallas forward kernels, whose residuals it keeps).  A part of ``bwd_ms``,
which holds it.  Interval arithmetic: a loop's envelope and its body are one
interval.  ``None`` in a cell that recomputes nothing.  Device trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, recomputed_only=True)
