"""Device milliseconds a step in which the op on the core is under none of
the program's blocks (``hvd_forward``, ``hvd_grad_allreduce``,
``hvd_optimizer_update``, ``hvd_loss_allreduce``): what no other per-layer
metric owns, so that it cannot grow unseen.  Interval arithmetic, not a sum
of durations: a compiler-made ``while`` is on the core's line as the
envelope of its own body's ops, and a sum would count the loop twice.
Device trace."""

from benchmarks.harness import trace

BLOCKS = ("hvd_forward", "hvd_grad_allreduce", "hvd_optimizer_update",
          "hvd_loss_allreduce")


def is_unscoped(op) -> bool:
    return not any(b in op.tf_op for b in BLOCKS)


def read(run):
    seconds = 0.0
    for chip in run.reduced.chips:
        mine = [(o.start, o.end) for o in chip.ops if is_unscoped(o)]
        owned = [(o.start, o.end) for o in chip.ops if not is_unscoped(o)]
        seconds += trace.total(trace.subtract(mine, owned))
    return run.per_step_ms(seconds / len(run.reduced.chips))
