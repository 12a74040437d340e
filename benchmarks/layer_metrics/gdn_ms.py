"""Device milliseconds a step spends in the gated-DeltaNet mixers: ops with
``hvd_gdn`` on their ``tf_op`` path (projections, convolution, the chunked
scan, the gated norm), forward and transposed.  Interval arithmetic: the
scan's ``while`` is on the core's line with its body.  Device trace."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    return parts.scope_ms(run, parts.under(parts.GDN))
