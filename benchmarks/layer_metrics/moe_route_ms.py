"""Device milliseconds a step spends routing: ops under ``hvd_moe_route``
(the router's product and softmax, top-k, the sort by expert, each tile's
rows gathered from the tokens and its weighted rows added back), forward and
transposed.  Interval arithmetic.  Device trace."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    return parts.scope_ms(run, parts.under(parts.MOE_ROUTE))
