"""The parts of a decoder layer and the layer recompute in a device trace.

The program (``horovod_tpu/models/scopes.py``, docs/profiling.md) puts
every matrix product, convolution and kernel call of a decoder layer and of
the head under exactly one named part, and JAX itself marks every op a
``jax.checkpoint`` computes a second time: its path reads
``.../checkpoint/rematted_computation/<layer>/<scopes>/<primitive>``.  A
reader here is an interval union over the ops whose ``tf_op`` path holds
one of its scopes as a whole component (``/hvd_gdn_in/``, so that a longer
name never answers for a shorter one), a chip at a time and the mean over
chips, as ``qwen3_next_parts.scope_ms`` reads: a loop's envelope and its
body are one interval.  ``None`` where no op matches: a program without the
scope (the parent of the PR that named it), or a cell whose model has no
such part.
"""

from __future__ import annotations

from typing import Optional, Sequence

from . import qwen3_next_parts as moe_parts

#: on the path of every op of a recomputed layer's second run (JAX 0.9.0;
#: the program holds the name in ``models/qwen3_next.REMAT_MARK`` and a
#: test of its own)
REMAT_MARK = "checkpoint/rematted_computation/"

# the program's device scopes (docs/profiling.md)
ATTN, ATTN_QKV, ATTN_OUT = "hvd_attn", "hvd_attn_qkv", "hvd_attn_out"
GDN, GDN_IN, GDN_CONV, GDN_OUT = ("hvd_gdn", "hvd_gdn_in", "hvd_gdn_conv",
                                  "hvd_gdn_out")
MLA, MLA_Q, MLA_OUT = "hvd_mla", "hvd_mla_q", "hvd_mla_out"
MOE, DENSE_MLP = "hvd_moe", "hvd_dense_mlp"
HEAD = "hvd_head"
FLASH_LAYOUT = "hvd_flash_layout"
MIXERS = (GDN, ATTN, MLA)


def scope_ms(run, scopes: Sequence[str] = (), *,
             recomputed_only: bool = False) -> Optional[float]:
    """Device milliseconds a step in ops under one of ``scopes`` (any op
    when empty), of the recompute alone with ``recomputed_only``.  A scope
    is matched as a whole component of the path: it is never the first one,
    which is the jitted function's, nor the last, which is the primitive."""
    needles = tuple(f"/{scope}/" for scope in scopes)

    def picks(op) -> bool:
        if recomputed_only and REMAT_MARK not in op.tf_op:
            return False
        return not needles or any(n in op.tf_op for n in needles)

    return moe_parts.scope_ms(run, picks)
