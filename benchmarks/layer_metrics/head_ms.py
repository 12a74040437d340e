"""Device milliseconds a step spends in the final norm and the head's
product: ops under ``hvd_head`` (``models/gpt.py``: the final ``LayerNorm``
and ``wte.attend``; the three decoders: the final RMSNorm and ``lm_head``),
forward and transposed.  The loss is outside the model and ``loss_ms``'s;
what XLA fuses into the head's two backward products (the cotangent of the
logits) carries the head's name and is here.  Interval arithmetic.  Device
trace."""

from benchmarks.harness import part_scopes as parts


def read(run):
    return parts.scope_ms(run, (parts.HEAD,))
