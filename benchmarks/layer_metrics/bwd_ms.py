"""Device milliseconds a step spends in the backward pass: ops under
``hvd_forward`` with ``transpose(`` on their ``tf_op`` path, what a
rematerialised forward recomputes there included.  With ``fwd_ms`` it adds
up to ``fwd_bwd_ms``.  Device trace."""


def is_backward(op) -> bool:
    return "hvd_forward" in op.tf_op and "transpose(" in op.tf_op


def read(run):
    return run.per_step_ms(run.reduced.op_seconds(is_backward))
