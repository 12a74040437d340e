"""Device milliseconds a step spends in the expert layers: ops under
``hvd_moe`` (router, sort, the loops over tiles: rows gathered, grouped
products, rows added back; shared expert), forward and transposed.  Interval
arithmetic.  Device trace."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    return parts.scope_ms(run, parts.under(parts.MOE))
