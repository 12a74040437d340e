"""Share of their roofline the grouped expert products reach: the least
time the chip's published peaks allow for the assignments an even router
sends to the held experts and those experts' weights read once a pass
(``harness.qwen3_next_parts.experts_train_required``) over the time of the
ops under ``hvd_moe_experts``.  A tile's empty rows, the rows an uneven
router sends beyond the even share and the forward products computed again
in the backward loop are not required work, so they lower the share."""

from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    cfg, mix = run.cell.cfg, run.cell.mix
    ops, nbytes, rows = parts.experts_train_required(
        cfg, int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0]))
    return parts.roofline(
        run, "moe_experts_roofline", parts.under(parts.MOE_EXPERTS),
        (ops, nbytes), f" ({rows:.0f} expected assignments a layer)")
