"""Device milliseconds a step spends in ops under the ``hvd_forward``
scope: forward and backward together, since the backward ops inherit the
scope.  Device trace."""


def read(run):
    return run.per_step_ms(
        run.reduced.op_seconds(lambda op: "hvd_forward" in op.tf_op))
