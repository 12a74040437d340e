"""Share of their roofline the convolutions reach: the least time the
chip's published peaks allow for the operations and bytes one step's 53
convolutions need (``harness.flops.conv_train_required``, from the cell's
shapes) over ``conv_ms``."""

from benchmarks.harness import flops
from benchmarks.layer_metrics.conv_ms import is_conv


def read(run):
    seconds = run.reduced.op_seconds(is_conv)
    if seconds <= 0:
        return None
    cfg, mix = run.cell.cfg, run.cell.mix
    need = flops.conv_train_required(
        flops.resnet50_convs(cfg, int(cfg["image_size"])),
        int(mix["rows_per_chip"]))
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"conv_roofline: {need[0]:.4g} operations and {need[1]:.4g} "
          f"bytes a step, {bound}-bound, least {least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / (seconds / run.steps)
