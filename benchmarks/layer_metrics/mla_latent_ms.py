"""Device milliseconds a step spends on what latent attention costs beyond
a plain k / v projection: ops under ``hvd_mla_latent`` (the down-projection
to the compressed kv and the shared rotary key, the norm on the compressed
kv, the up-projection to every head's ``[k_nope | v]``, the shared key's
rotary embedding, assembling ``k`` a head), forward and transposed.
Interval arithmetic.  Device trace."""

from benchmarks.harness import kanana2_parts as parts


def read(run):
    return parts.scope_ms(run, parts.MLA_LATENT)
