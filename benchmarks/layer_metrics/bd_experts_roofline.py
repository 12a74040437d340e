"""Share of their roofline the grouped expert products reach where the
decoder runs two rows a data token (block diffusion): the least time the
chip's published peaks allow for the assignments an even router sends to
the held experts over the ``2 L`` rows of a sequence and those experts'
weights read once a pass (``harness.sdar_parts.experts_train_required``)
over the time of the ops under ``hvd_moe_experts``.  A tile's empty rows,
the rows an uneven router sends beyond the even share and the forward
products computed again in the backward loop are not required work, so
they lower the share."""

from benchmarks.harness import qwen3_next_parts as moe_parts
from benchmarks.harness import sdar_parts as parts


def read(run):
    cfg, mix = run.cell.cfg, run.cell.mix
    if "block_length" not in cfg:
        return None
    ops, nbytes, rows = parts.experts_train_required(
        cfg, int(mix["rows_per_chip"]), int(mix["arrays"][0]["shape"][0]))
    return moe_parts.roofline(
        run, "bd_experts_roofline", moe_parts.under(moe_parts.MOE_EXPERTS),
        (ops, nbytes), f" ({rows:.0f} expected assignments a layer)")
