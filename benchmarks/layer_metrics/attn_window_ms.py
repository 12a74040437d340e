"""Device milliseconds a step spends in the attention of the sliding-window
layers, whole: ops with ``hvd_attn_window`` on their ``tf_op`` path as a
whole component (``models/mellum2.Attention`` of a window layer: the q / k /
v projections, rotary, the k / v repeat, the three flash kernels under the
window mask, their layout swaps, ``o_proj``), first run, recompute and
transposes.  Interval arithmetic.  Device trace."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.kind_ms(run, parts.SLIDING)
