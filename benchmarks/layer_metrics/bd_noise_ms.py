"""Device milliseconds a step spends making the block-diffusion input: ops
under ``hvd_bd_noise`` (the compare of the draws with the blocks' levels,
the ``[MASK]`` substitution, the concatenation of the noised and the clean
copy, the position ids and their rotary tables), forward and transposed.
Interval arithmetic.  Device trace."""

from benchmarks.harness import qwen3_next_parts as moe_parts
from benchmarks.harness import sdar_parts as parts


def read(run):
    return moe_parts.scope_ms(run, moe_parts.under(parts.BD_NOISE))
