"""Device milliseconds a step spends in the loss: ops with the program's
``hvd_loss`` scope on their ``tf_op`` path (``training.py`` puts it round
``loss_fn(logits, y)``, inside ``hvd_forward``), forward and transposed.
``hvd_loss/`` with its slash, so that ``hvd_loss_allreduce`` (the reported
loss's average over ranks) is not read with it.  Interval arithmetic: a
compiler-made ``while`` is on the core's line with its body.  What XLA
fuses into a neighbour (a cotangent computed inside the head's backward
matmul) carries the neighbour's name and is that block's time.  Device
trace."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    return parts.scope_ms(run, parts.under("hvd_loss/"))
