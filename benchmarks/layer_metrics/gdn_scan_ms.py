"""Device milliseconds a step spends in the chunked gated delta rule
(``ops/gated_delta.py``): ops under ``hvd_gdn_scan``, the chunk-local
products and the scan over the chunks, forward and transposed.  Interval
arithmetic.  Device trace."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    return parts.scope_ms(run, parts.under(parts.GDN_SCAN))
