"""Device milliseconds a step spends in the attention of the full (causal)
layers that stand beside sliding-window layers, whole: ops with
``hvd_attn_full`` on their ``tf_op`` path as a whole component
(``models/mellum2.Attention`` of a full layer: the q / k / v projections,
rotary from the YaRN table, the k / v repeat, the three flash kernels under
the causal mask, their layout swaps, ``o_proj``), first run, recompute and
transposes.  Interval arithmetic.  Device trace."""

from benchmarks.harness import mellum2_parts as parts


def read(run):
    return parts.kind_ms(run, parts.FULL)
