"""Share of its roofline the flash kernels reach: the least time the chip's
published peaks allow for the operations and bytes one step's attention
needs (``harness.flops.flash_train_required``: causal, forward and
backward, from the cell's shapes) over ``flash_ms``."""

from benchmarks.harness import flops, trace


def read(run):
    seconds = run.reduced.op_seconds(trace.is_mosaic_kernel)
    if seconds <= 0:
        return None
    cfg, mix = run.cell.cfg, run.cell.mix
    need = flops.flash_train_required(
        int(mix["rows_per_chip"]), cfg["n_head"],
        int(mix["arrays"][0]["shape"][0]), cfg["n_embd"] // cfg["n_head"],
        causal=True, layers=cfg["n_layer"])
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"flash_roofline: {need[0]:.4g} operations and {need[1]:.4g} "
          f"bytes a step, {bound}-bound, least {least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / (seconds / run.steps)
