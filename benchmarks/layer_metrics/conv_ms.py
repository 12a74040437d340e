"""Device milliseconds a step spends in ops whose scope ends in
``conv_general_dilated``: XLA's convolutions, forward and both gradients,
with what the compiler fused into them.  Device trace."""


def is_conv(op) -> bool:
    return "conv_general_dilated" in op.tf_op


def read(run):
    seconds = run.reduced.op_seconds(is_conv)
    return run.per_step_ms(seconds) if seconds > 0 else None
