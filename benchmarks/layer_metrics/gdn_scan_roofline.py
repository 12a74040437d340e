"""Share of its roofline the chunked gated delta rule reaches: the least
time the chip's published peaks allow for what the recurrence requires
(``harness.qwen3_next_parts.scan_train_required``: three ``dk x dv``
products a head a token forward, the backward pass counted the same way;
q, k, v, g, beta in, o out and the float32 chunk states that cross HBM)
over ``gdn_scan_ms``.  The chunk algebra's own products are not required
work, so they lower the share."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    cfg, mix = run.cell.cfg, run.cell.mix
    need = parts.scan_train_required(
        cfg, int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0]))
    return parts.roofline(run, "gdn_scan_roofline",
                          parts.under(parts.GDN_SCAN), need)
