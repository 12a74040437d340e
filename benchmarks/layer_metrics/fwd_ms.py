"""Device milliseconds a step spends in the forward pass: ops under
``hvd_forward`` with no ``transpose(`` on their ``tf_op`` path (the
backward ops are the transposed ones).  A fusion that holds ops of both
passes is counted where its root is.  With ``bwd_ms`` it adds up to
``fwd_bwd_ms``.  Device trace."""


def is_forward(op) -> bool:
    return "hvd_forward" in op.tf_op and "transpose(" not in op.tf_op


def read(run):
    return run.per_step_ms(run.reduced.op_seconds(is_forward))
