"""Device milliseconds a step spends under ``hvd_grad_allreduce`` in ops
that are not an all-reduce by opcode: what bucketing itself costs (each
bucket's concatenate, slices and the division of an average), on one chip
as on four.  Device trace."""

from benchmarks.harness import trace


def is_pack(op) -> bool:
    return "hvd_grad_allreduce" in op.tf_op and not trace.is_allreduce(op)


def read(run):
    seconds = run.reduced.op_seconds(is_pack)
    return run.per_step_ms(seconds) if seconds > 0 else None
