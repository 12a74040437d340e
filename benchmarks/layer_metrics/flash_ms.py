"""Device milliseconds a step spends in Mosaic kernels.  The step's only
``tpu_custom_call``s are the flash attention forward, dq and dkv kernels
(36 in GPT-2-small), so they are found by their call target.  Device
trace."""

from benchmarks.harness import trace


def read(run):
    seconds = run.reduced.op_seconds(trace.is_mosaic_kernel)
    return run.per_step_ms(seconds) if seconds > 0 else None
