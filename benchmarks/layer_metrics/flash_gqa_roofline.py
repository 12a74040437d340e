"""Share of their roofline the flash kernels reach in a model whose
attention layers are some of its layers and name their heads apart from
their width (``num_attention_heads``, ``head_dim``, one layer in
``full_attention_interval``): the least time the chip's published peaks
allow for the seven products one step's causal attention needs over the q
heads (``harness.flops.flash_train_required``; k and v count at the q
heads' number, as the kernels take them) over ``flash_ms``.  A recomputed
forward kernel is time and not required work, so it lowers the share."""

from benchmarks.harness import flops, trace
from benchmarks.harness import qwen3_next_parts as parts


def read(run):
    cfg, mix = run.cell.cfg, run.cell.mix
    seconds = run.reduced.op_seconds(trace.is_mosaic_kernel)
    if seconds <= 0 or "full_attention_interval" not in cfg:
        return None
    need = flops.flash_train_required(
        int(mix["rows_per_chip"]), cfg["num_attention_heads"],
        int(mix["arrays"][0]["shape"][0]), cfg["head_dim"], causal=True,
        layers=parts.layer_counts(cfg)[1])
    least, bound = flops.least_seconds(*need, run.peak)
    print(f"flash_gqa_roofline: {need[0]:.4g} operations and {need[1]:.4g} "
          f"bytes a step, {bound}-bound, least {least * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / (seconds / run.steps)
