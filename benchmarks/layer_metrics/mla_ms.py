"""Device milliseconds a step spends in the latent-attention mixers: ops
with ``hvd_mla`` on their ``tf_op`` path (the q projection and its rotary
part, the latent path, the three flash kernels, ``o_proj``), forward and
transposed.  Interval arithmetic.  Device trace."""

from benchmarks.harness import kanana2_parts as parts


def read(run):
    return parts.scope_ms(run, parts.MLA)
